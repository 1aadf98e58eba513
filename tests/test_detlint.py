"""The detlint rule framework (tools/detlint/) and the env-var registry.

Two layers: each rule fires on a seeded violation and stays quiet on the
clean twin (rule unit tests over parsed snippets), and the repo itself is
lint-clean (the dogfood gate — the same invocation `make lint` runs).
No jax involved anywhere here; detlint is pure AST.
"""

import ast
import subprocess
import sys

import pytest

from distributed_embeddings_tpu.utils import envvars
from tools import detlint
from tools.detlint.rules import (bare_except, donated_aux, env_registry,
                                 hardcoded_capacity, host_fetch, module_scope_jax, named_scope,
                                 spawn_context, thread_shared,
                                 unsized_unique)

CTX = {"repo": detlint.REPO}
PARALLEL = "distributed_embeddings_tpu/parallel/x.py"


def _check(rule, src, path=PARALLEL):
    return rule.check(ast.parse(src), path, src, dict(CTX))


# ------------------------------------------------------------ rule units


def test_bare_except_fires_and_clean():
    assert _check(bare_except, "try:\n    pass\nexcept:\n    pass\n")
    assert not _check(bare_except,
                      "try:\n    pass\nexcept Exception:\n    pass\n")


def test_env_registry_literal_and_constant_resolution():
    assert _check(env_registry,
                  'import os\nv = os.environ.get("DETPU_NOT_A_KNOB")\n')
    assert _check(env_registry,
                  'import os\nX = "DETPU_NOT_A_KNOB"\nv = os.environ[X]\n')
    assert _check(env_registry,
                  'import os\nv = os.getenv("DETPU_NOT_A_KNOB")\n')
    # registered names, writes, and non-DETPU names all pass
    assert not _check(env_registry,
                      'import os\nv = os.environ.get("DETPU_OBS")\n')
    assert not _check(env_registry,
                      'import os\nos.environ["DETPU_NOT_A_KNOB"] = "1"\n')
    assert not _check(env_registry,
                      'import os\nv = os.environ.get("HOME")\n')


def test_host_fetch_rule():
    assert _check(host_fetch, "def f(x):\n    return x.item()\n")
    assert _check(host_fetch,
                  "import jax\ndef f(x):\n    return jax.device_get(x)\n")
    assert not _check(host_fetch,
                      "def f(x):\n    return x.item()  # host-ok: driver\n")
    # .item(key) (dict-style with args) is not an array readback
    assert not _check(host_fetch, "def f(d):\n    return d.item(3)\n")


def test_named_scope_rule():
    bad = ("from jax import lax\n"
           "def f(x):\n"
           "    return lax.all_to_all(x, 'data', 0, 0)\n")
    assert _check(named_scope, bad)
    ok = ("from jax import lax\n"
          "def f(x):\n"
          "    with obs.scope('id_all_to_all'):\n"
          "        return lax.all_to_all(x, 'data', 0, 0)\n")
    assert not _check(named_scope, ok)


def test_unsized_unique_rule():
    """The seeded-violation drill: jnp.unique/nonzero without size= in
    package code fires; size=, the unsized-ok marker, host-side numpy,
    and out-of-package paths stay quiet."""
    path = "distributed_embeddings_tpu/analysis/x.py"
    bad = ("import jax.numpy as jnp\n"
           "def f(ids):\n"
           "    return jnp.unique(ids)\n")
    assert _check(unsized_unique, bad, path=path)
    assert _check(unsized_unique,
                  "import jax\n"
                  "def f(x):\n"
                  "    return jax.numpy.nonzero(x)\n", path=path)
    ok = ("import jax.numpy as jnp\n"
          "def f(ids):\n"
          "    return jnp.unique(ids, size=32, fill_value=0)\n")
    assert not _check(unsized_unique, ok, path=path)
    annotated = ("import jax.numpy as jnp\n"
                 "def f(ids):\n"
                 "    return jnp.unique(ids)  # unsized-ok: eager tooling\n")
    assert not _check(unsized_unique, annotated, path=path)
    # host-side numpy is a different module
    assert not _check(unsized_unique,
                      "import numpy as np\n"
                      "def f(x):\n"
                      "    return np.unique(x)\n", path=path)
    # the rule is scoped to package code only (the runner's SCOPE filter)
    assert detlint._matches(path, unsized_unique.SCOPE)
    assert not detlint._matches("tools/x.py", unsized_unique.SCOPE)


def test_hardcoded_capacity_rule():
    """The seeded drills: a capacity-named constant and a byte-scale
    literal in package code fire; the marker, small literals, hex hash
    constants, and the registry module itself stay quiet."""
    path = "distributed_embeddings_tpu/parallel/x.py"
    # seeded capacity constant (any magnitude) fires
    assert _check(hardcoded_capacity, "V5E_HBM_GB = 16\n", path=path)
    # seeded byte-scale literal fires
    assert _check(hardcoded_capacity,
                  "LIMIT = 17179869184\n", path=path)
    assert _check(hardcoded_capacity,
                  "def f():\n    return 2.7e9\n", path=path)
    # the marker escapes both triggers
    assert not _check(
        hardcoded_capacity,
        "V5E_HBM_GB = 16  # capacity-ok: doc example\n", path=path)
    assert not _check(
        hardcoded_capacity,
        "VOCAB = 2000000000  # capacity-ok: model size\n", path=path)
    # small non-capacity constants and hex bit patterns stay quiet
    assert not _check(hardcoded_capacity, "CHUNK = 128 * 1024 * 1024\n",
                      path=path)
    assert not _check(hardcoded_capacity, "MASK = 0xFFFFFFFFFF\n",
                      path=path)
    # the registry module is the one legitimate home (EXCLUDE'd)
    assert detlint._matches(
        "distributed_embeddings_tpu/analysis/plan_audit.py",
        hardcoded_capacity.EXCLUDE)
    assert not detlint._matches("chip_smoke.py", hardcoded_capacity.SCOPE)


def test_module_scope_jax_rule():
    path = "distributed_embeddings_tpu/utils/obs.py"
    assert _check(module_scope_jax, "import jax\n", path=path)
    assert _check(module_scope_jax, "from jax import lax\n", path=path)
    assert not _check(module_scope_jax,
                      "def f():\n    import jax\n    return jax\n",
                      path=path)


# ------------------------------------------------------- framework pieces


def test_donated_aux_registry_resolves():
    reg = donated_aux.registered_aux(detlint.REPO, dict(CTX))
    # the two aux kinds the step builders thread today, in signature
    # order (telemetry first, then streaming — the _with_aux_signature
    # contract)
    assert reg == [("telemetry", "telem"), ("streaming", "stream")]


def test_donated_aux_wrong_order_and_undeclared_drills():
    # seeded wrong-order drill: streaming threaded BEFORE telemetry —
    # jit donation indices and the resilient rewind would then address
    # the wrong buffer
    bad_order = ("def step(state, cat_inputs, batch, stream, telem):\n"
                 "    pass\n")
    found = _check(donated_aux, bad_order)
    assert found and "out of registry order" in found[0].message
    # seeded undeclared drill: a new aux kind threaded without being
    # registered first
    undeclared = ("def step(state, cat_inputs, batch, telem, sched):\n"
                  "    pass\n")
    found = _check(donated_aux, undeclared)
    assert found and "undeclared aux arg 'sched'" in found[0].message


def test_donated_aux_clean_twins():
    for ok in (
        "def step(state, cat_inputs, batch, telem, stream):\n    pass\n",
        "def step(state, cat_inputs, batch, telem):\n    pass\n",
        "def loop(state, cat_stacks, batch_stacks, stream):\n    pass\n",
        # the packed-tuple internal form is exempt (not a jit boundary)
        "def core(state, cat_inputs, batch, aux):\n    pass\n",
        # no trailing aux at all
        "def step(state, cat_inputs, batch):\n    pass\n",
        # not a step-builder signature
        "def f(a, b, c, d):\n    pass\n",
    ):
        assert not _check(donated_aux, ok), ok


def test_spawn_context_rule():
    """Seeded drill: default-context multiprocessing in package code
    fires; the spawn idiom, process-free submodules, and the spawn-ok
    waiver stay quiet."""
    # raw-module factories = default (fork) context
    assert _check(spawn_context,
                  "import multiprocessing\n"
                  "p = multiprocessing.Process(target=f)\n")
    assert _check(spawn_context,
                  "import multiprocessing as mp\n"
                  "pool = mp.Pool(4)\n")
    # importing the factory binds the default context at the import
    assert _check(spawn_context, "from multiprocessing import Process\n")
    assert _check(spawn_context, "from multiprocessing.pool import Pool\n")
    # asking for fork (or the platform default) by name
    assert _check(spawn_context,
                  "import multiprocessing\n"
                  'ctx = multiprocessing.get_context("fork")\n')
    assert _check(spawn_context,
                  "import multiprocessing\n"
                  "ctx = multiprocessing.get_context()\n")
    assert _check(spawn_context,
                  "from multiprocessing import set_start_method\n"
                  'set_start_method("forkserver")\n')
    # the blessed idiom: explicit spawn, factories off the spawn context
    ok = ("import multiprocessing\n"
          '_SPAWN = multiprocessing.get_context("spawn")\n'
          "p = _SPAWN.Process(target=f)\n")
    assert not _check(spawn_context, ok)
    assert not _check(spawn_context,
                      "from multiprocessing import get_context\n"
                      'ctx = get_context(method="spawn")\n')
    # process-free corners start nothing
    assert not _check(spawn_context,
                      "from multiprocessing import shared_memory\n"
                      "from multiprocessing.connection import Client\n"
                      "from multiprocessing import resource_tracker\n")
    # the waiver
    assert not _check(spawn_context,
                      "import multiprocessing\n"
                      "p = multiprocessing.Process(target=f)"
                      "  # spawn-ok: no jax in this process\n")
    # out of scope (scoping is the runner's job): tests may fork freely
    assert not detlint._matches("tests/test_shm.py", spawn_context.SCOPE)
    assert detlint._matches(
        "distributed_embeddings_tpu/parallel/supervisor.py",
        spawn_context.SCOPE)


def test_thread_shared_rule():
    """Seeded drill: a thread-spawning class without a _THREAD_SHARED
    declaration fires; the declared twin, the empty-tuple declaration,
    the waiver, spawn-free classes, and module-level spawns stay quiet."""
    spawning = ("import threading\n"
                "class Driver:\n"
                "    def start(self):\n"
                "        threading.Thread(target=self._run).start()\n")
    found = _check(thread_shared, spawning)
    assert found and "_THREAD_SHARED" in found[0].message
    # Thread subclasses spawn themselves — same obligation
    assert _check(thread_shared,
                  "from threading import Thread\n"
                  "class W(Thread):\n"
                  "    def run(self):\n"
                  "        pass\n")
    # a non-tuple declaration is its own finding (the auditor parses it)
    bad_decl = ("import threading\n"
                "class Driver:\n"
                "    _THREAD_SHARED = ['_x']\n"
                "    def start(self):\n"
                "        threading.Thread(target=self._run).start()\n")
    found = _check(thread_shared, bad_decl)
    assert found and "literal tuple" in found[0].message
    # the declared twin (non-empty and empty both count)
    assert not _check(thread_shared,
                      "import threading\n"
                      "class Driver:\n"
                      '    _THREAD_SHARED = ("_results",)\n'
                      "    def start(self):\n"
                      "        threading.Thread(target=self._run).start()\n")
    assert not _check(thread_shared,
                      "import threading\n"
                      "class Driver:\n"
                      "    _THREAD_SHARED = ()\n"
                      "    def start(self):\n"
                      "        threading.Thread(target=self._run).start()\n")
    # the waiver on the spawn line
    assert not _check(thread_shared,
                      "import threading\n"
                      "class Driver:\n"
                      "    def start(self):\n"
                      "        threading.Thread(target=f).start()"
                      "  # thread-shared-ok: script helper\n")
    # spawn-free classes and module-level spawns carry no obligation
    assert not _check(thread_shared,
                      "import threading\n"
                      "class Plain:\n"
                      "    pass\n"
                      "threading.Thread(target=f).start()\n")
    # scoped to the package; tests/tools may spawn undeclared
    assert detlint._matches(
        "distributed_embeddings_tpu/parallel/serving.py",
        thread_shared.SCOPE)
    assert not detlint._matches("tests/test_shm.py", thread_shared.SCOPE)
    assert not detlint._matches("tools/x.py", thread_shared.SCOPE)


def test_discover_rules_finds_all():
    rules = detlint.discover_rules()
    assert {"bare-except", "env-registry",
            "hardcoded-capacity", "host-fetch", "module-scope-jax",
            "named-scope-exchange", "spawn-context", "thread-shared",
            "unsized-unique"} <= set(rules)


def test_unknown_rule_name_raises():
    with pytest.raises(ValueError, match="unknown detlint rule"):
        detlint.run(rule_names=["no-such-rule"])


def test_repo_is_lint_clean():
    """Dogfood: the tree ships with zero findings (the make lint gate)."""
    findings = detlint.run()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_clean_and_seeded(tmp_path):
    """End-to-end CLI: clean repo exits 0; a seeded unregistered env read
    (written under a real checked path inside a scratch repo copy is
    overkill — a direct rule-scoped file list does it) exits 1."""
    r = subprocess.run([sys.executable, "-m", "tools.detlint"],
                       cwd=detlint.REPO, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_registry_roundtrip():
    """The AST-extracted registry matches the imported module's view, and
    the runtime helpers enforce membership."""
    names = env_registry.registered_names(detlint.REPO)
    assert names == set(envvars.registered())
    assert "DETPU_OBS" in names and "DETPU_FAULT" in names
    with pytest.raises(KeyError, match="not a registered"):
        envvars.get("DETPU_NOT_A_KNOB")
    with pytest.raises(KeyError):
        envvars.enabled("DETPU_NOT_A_KNOB")


def test_envvars_semantics(monkeypatch):
    monkeypatch.delenv("DETPU_NANGUARD", raising=False)
    assert envvars.enabled("DETPU_NANGUARD")  # declared default "1"
    monkeypatch.setenv("DETPU_NANGUARD", "0")
    assert not envvars.enabled("DETPU_NANGUARD")
    monkeypatch.setenv("DETPU_NANGUARD_K", "7")
    assert envvars.get_int("DETPU_NANGUARD_K", 3) == 7
    monkeypatch.setenv("DETPU_NANGUARD_K", "bogus")
    assert envvars.get_int("DETPU_NANGUARD_K", 3) == 3
    monkeypatch.setenv("DETPU_PROBE_TIMEOUT_S", "2.5")
    assert envvars.get_float("DETPU_PROBE_TIMEOUT_S") == 2.5
