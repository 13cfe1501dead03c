"""The one general traffic generator: token batches from a traffic file's
parameters and ``--seed``. Runs in the driver (numpy only, no jax).

Every seed gives the same amount of work: the number and shape of rows are
fixed by the traffic file, only their contents differ.
"""

from __future__ import annotations

import json
import os

import numpy as np


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resident_tokens(seed: int, traffic: dict, vocab_size: int) -> np.ndarray:
    """[batch, seq + 1] uniform random tokens: the one batch a step cell
    keeps on the device."""
    rng = np.random.default_rng([seed, 1])
    return rng.integers(0, vocab_size, (traffic["batch"], traffic["seq"] + 1),
                        dtype=np.int32)


def document_lengths(rng, n_tokens: int, doc: dict) -> np.ndarray:
    """Document lengths (without separator) that together cover at least
    ``n_tokens``."""
    if doc["length"] != "lognormal":
        raise ValueError(f"unknown document length law {doc['length']!r}")
    mu, sigma = np.log(doc["median_tokens"]), doc["sigma"]
    out, covered = [], 0
    while covered < n_tokens:
        n = max(64, int(2 * n_tokens / doc["median_tokens"]))
        lens = np.maximum(doc["min_tokens"],
                          rng.lognormal(mu, sigma, n)).astype(np.int64)
        out.append(lens)
        covered += int(lens.sum()) + n
    return np.concatenate(out)


def packed_rows(seed: int, traffic: dict, vocab_size: int) -> np.ndarray:
    """[dataset_steps * batch, seq + 1] rows of documents packed end to
    end, one separator after each document, as a pre-tokenised corpus is
    packed for pre-training."""
    doc = traffic["documents"]
    rows = traffic["dataset_steps"] * traffic["batch"]
    width = traffic["seq"] + 1
    total = rows * width
    rng = np.random.default_rng([seed, 2])
    sep = doc["separator_id"]
    # every id but the separator's
    tokens = rng.integers(0, vocab_size - 1, total, dtype=np.int32)
    tokens[tokens >= sep] += 1
    ends = np.cumsum(document_lengths(rng, total, doc) + 1) - 1
    tokens[ends[ends < total]] = sep
    return tokens.reshape(rows, width)


def dataset_blocks(seed: int, traffic: dict, vocab_size: int) -> list:
    rows = packed_rows(seed, traffic, vocab_size)
    step = traffic["block_rows"]
    return [rows[i:i + step] for i in range(0, len(rows), step)]


def traffic_path(root: str, directory: str, name: str) -> str:
    path = os.path.join(root, directory, name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no traffic file {name}.json under {directory}/")
    return path
