"""save_commit_s: median length of the ``save/commit`` records (the thread
that writes a save's files behind the next steps) in the train worker's ring
up to the traced window's end; whole records."""

from perfbench import clusterspans


def read(r):
    return clusterspans.save_commit_s(r)
