"""The system under test where no model is in it: the compile cache and its
counters, and the serving runtime's request and result types. Like a family,
it takes from the program its entry points and nothing that decides a metric
or ``correct``; the runner, ``run.py`` and the tools ask it, so that a family
brings only what knows its model.
"""

from __future__ import annotations

import jax

from distributed_embeddings_tpu.parallel import serving as serving_mod
from distributed_embeddings_tpu.utils import obs, runtime


def ensure_compile_cache() -> str:
    """The persistent compile cache at the program's fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every
    program of a run, the small ones too."""
    path = runtime.ensure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


install_compile_listener = obs.install_compile_listener
# a snapshot of every process counter of the program (``obs.counter_inc``):
# the runner takes one as the window opens and one as it closes
counters = obs.counters
Request = serving_mod.Request
Served = serving_mod.Served


def is_refused(result) -> bool:
    """A result the system refused or lost: anything but ``Served``."""
    return not isinstance(result, serving_mod.Served)
