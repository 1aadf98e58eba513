"""Operations and bytes that each kernel's work needs, from the cell's shapes
alone, so that a roofline share reads the same work whatever implements it.
``WORK`` and ``FLOPS`` are what the readers ``roofline`` and ``mfu`` find by
the name in a metric's file."""

from __future__ import annotations

import numpy as np

from .traffic import live_ids

_BYTES = {"bfloat16": 2, "float32": 4}


def dense_forward_flops_per_sample(config: dict) -> float:
    """Matmul FLOP of one sample's forward pass: both MLPs and the Gram
    matrix of the dot interaction (the count of the program's
    ``bench.dense_flops_per_sample`` without its factor 3, copied)."""
    dims = [int(config["num_numerical"])] + list(config["bottom_mlp"])
    f = sum(2 * a * b for a, b in zip(dims, dims[1:]))
    nf = len(config["table_sizes"]) + 1
    dim = int(config["embedding_dim"])
    f += 2 * nf * nf * dim
    dims = [nf * (nf - 1) // 2 + dim] + list(config["top_mlp"])
    return float(f + sum(2 * a * b for a, b in zip(dims, dims[1:])))


def dense_train_flops_per_sample(config: dict) -> float:
    """Forward, input gradient and weight gradient; nothing recomputed."""
    return 3.0 * dense_forward_flops_per_sample(config)


def lookup_bytes(config: dict, ids: float, outputs: float) -> float:
    """HBM bytes of a forward lookup: ``ids`` table rows read and ``outputs``
    combined rows written in the compute dtype."""
    dim = int(config["embedding_dim"])
    return dim * (ids * _BYTES[config["table_dtype"]]
                  + outputs * _BYTES[config["compute_dtype"]])


def apply_bytes(config: dict, ids: float, distinct_rows: float) -> float:
    """HBM bytes of a sparse SGD apply: each distinct touched row read and
    written once, and one gradient row streamed in for every id."""
    dim = int(config["embedding_dim"])
    return dim * (2 * distinct_rows * _BYTES[config["table_dtype"]]
                  + ids * _BYTES[config["compute_dtype"]])


def step_work(config: dict, traffic: dict, batches) -> dict:
    """A step's work for the rooflines, from the staged batches alone."""
    live = [live_ids(b) for b in batches]
    return {
        "ids_per_step": float(np.mean([sum(len(i) for i in b)
                                       for b in live])),
        "distinct_rows_per_step": float(np.mean(
            [sum(len(np.unique(i)) for i in b) for b in live])),
        "outputs_per_step": float(len(config["table_sizes"])
                                  * int(traffic["global_batch"]))}


# name in a metric's file -> (config, work, ctx) -> (FLOP, HBM bytes) a step
WORK = {
    "lookup": lambda cfg, w, ctx: (0.0, lookup_bytes(
        cfg, w["ids_per_step"], w["outputs_per_step"])),
    "apply": lambda cfg, w, ctx: (0.0, apply_bytes(
        cfg, w["ids_per_step"], w["distinct_rows_per_step"])),
    "dense_train": lambda cfg, w, ctx: (
        dense_train_flops_per_sample(cfg) * ctx["samples"] / ctx["steps"],
        0.0),
}

# name in a metric's file -> (config) -> the model's FLOP a sample
FLOPS = {"dense_train": dense_train_flops_per_sample,
         "dense_forward": dense_forward_flops_per_sample}
