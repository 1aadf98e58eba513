"""Family ``moe_lm``: a sparse-expert language model (window and
no-position full attention, a ReGLU expert layer routed ahead of attention,
one chip's share of the experts and of the vocabulary) trained through
``DistributedEmbedding`` and ``make_hybrid_train_step``. What the runner and
the tools call (``benchmarks/families/__init__.py`` says what each is) is
here; the model's keys are read in this package and nowhere else.

``program.py`` is the adapter half; ``reference.py``, ``weights.py`` and
``work.py`` import nothing of the program; ``traffic.py`` makes the batches;
``train.py`` holds the two sides together. A sample is a sequence.
"""

import sys

from benchmarks.families import system
from benchmarks.lib.train import IN_FLIGHT

from . import program, reference, traffic, train, weights, work
from .program import build, stage
from .train import first_steps, train_numbers
from .work import FLOPS, WORK, step_work

# the control (the reference in the nearest precision below the bfloat16 that
# the configuration states) and the fault that the reference can plant
CONTROL_PRECISION = "float8"
REFERENCE_FAULTS = ("half_batch",)


def train_batches(config: dict, tr: dict, seed: int):
    return traffic.train_batches(tr, int(config["vocab_size"]),
                                 int(config["train_sequence_length"]), seed)


def train_step(built: program.Built, tr: dict):
    # a step's counts are read once the window has waited for that step: one
    # more than it keeps in flight
    return program.train_step(built, tr, lag=IN_FLIGHT + 1)


def reference_numbers(config: dict, tr: dict, batches, seed: int, **kw):
    # the runner asks for the reference once the window has closed: standard
    # error's account of what the steps read so far routed
    print("routing counted so far: " + ", ".join(
        f"{k} {v}" for k, v in sorted(system.counters().items())
        if k.startswith("moe_")), file=sys.stderr)
    return train.reference_numbers(config, tr, batches, seed, **kw)


def samples_per_step(config: dict, tr: dict) -> int:
    return int(tr["sequences"])
