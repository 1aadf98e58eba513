"""Family ``dlrm``: MLPerf DLRM (dot interaction) over a list of embedding
tables, through ``DistributedEmbedding``, ``make_hybrid_train_step`` and
``ServingRuntime``. What the runner and the tools call
(``benchmarks/families/__init__.py`` says what each is) is here; the model's
keys (``table_sizes``, ``num_numerical``, the MLPs, the two learning rates of
a training traffic file) are read in this package and nowhere else.

``program.py`` is the adapter half; ``reference.py``, ``weights.py`` and
``work.py`` import nothing of the program; ``traffic.py`` makes the batches
and the requests; ``train.py`` and ``serve.py`` hold the two sides together
for a cell of either kind.
"""

from . import program, reference, serve, traffic, train, weights, work
from .program import exchange_left_out, serving_runtime, stage
from .serve import compare as serve_numbers
from .serve import reference_logits as reference_answers
from .serve import requests_of
from .train import first_steps, reference_numbers, train_numbers
from .work import FLOPS, WORK, step_work

# the control (the reference in the nearest precision below the bfloat16 that
# the configurations state) and the fault that the reference can plant
CONTROL_PRECISION = "float8"
REFERENCE_FAULTS = ("half_batch",)


def build(config: dict, tr: dict, seed: int) -> program.Built:
    hot = tr.get("hotness")
    return program.build(
        config, seed,
        combiner="sum" if hot and hot["kind"] != "one" else None,
        dense_lr=float(tr.get("dense_lr", 0.0)))


def train_batches(config: dict, tr: dict, seed: int):
    return traffic.train_batches(tr, config["table_sizes"],
                                 int(config["num_numerical"]), seed)


def train_step(built: program.Built, tr: dict):
    return program.train_step(built, float(tr["emb_lr"]),
                              float(tr["dense_lr"]))


def samples_per_step(config: dict, tr: dict) -> int:
    return int(tr["global_batch"])


def serve_schedule(config: dict, tr: dict, seed: int, seconds: float):
    return traffic.serve_schedule(tr, config["table_sizes"],
                                  int(config["num_numerical"]), seed, seconds)
