"""Family ``mla_lm``: a latent-attention expert language model (multi-head
latent attention, a leading dense layer, sigmoid group-limited routing beside
a shared expert, one chip's share of the experts and of the vocabulary)
served a session at a time through ``DistributedEmbedding`` and
``SessionRuntime``. What the runner and the tools call
(``benchmarks/families/__init__.py`` says what each is) is here; the model's
keys are read in this package and nowhere else.

``program.py`` is the adapter half; ``reference.py``, ``weights.py`` and
``work.py`` import nothing of the program; ``traffic.py`` makes the documents
and the turns; ``serve.py`` holds the two sides together. A sample is a
generated token; a request is a turn of a session. ``readings.py`` reads the
numbers the cell's limits are set from (``benchmarks/tools/limits.py``
cannot: this reference needs the served tokens).
"""

from . import program, reference, serve, traffic, weights, work
from .program import build, serving_runtime
from .serve import compare as serve_numbers
from .serve import reference_answers, requests_of
from .work import FLOPS, WORK

# the control (the reference in the nearest precision below the bfloat16 that
# the configuration states) and the fault that the reference can plant
CONTROL_PRECISION = "float8"
REFERENCE_FAULTS = ("no_mscale",)


def serve_schedule(config: dict, tr: dict, seed: int, seconds: float):
    return traffic.serve_schedule(tr, int(config["vocab_size"]), seed,
                                  seconds)
