"""worker_boot_s: the whole length of the train worker's ``worker/boot``
(process start -> registered with the raylet and ready for work), the longest
where a gang has several workers; whether it began before the gang's start
(the raylet starts processes ahead) or inside it."""

from perfbench import clusterspans


def read(r):
    return clusterspans.worker_boot_s(r)
