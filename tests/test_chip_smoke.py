"""chip_smoke.py's contract, as far as a machine without a chip can hold it:
the explicit dry mode runs every phase on the CPU at a toy size, no chip and
no dry option is a non-zero exit with no result line, and a failed check
exits non-zero naming the check. Plus the compile-cache helper every entry
point calls first."""

import os
import re
import subprocess
import sys

import pytest

from distributed_embeddings_tpu.utils import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, **env):
    # the cache directory is placed from outside, so nothing is written
    # into the checkout; chiprun_out/ is where the script may write
    full = {**os.environ, "JAX_PLATFORMS": "cpu", "DETPU_FAULT": "",
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"), **env}
    return subprocess.run([sys.executable, SMOKE] + args, cwd=str(tmp_path),
                          env=full, capture_output=True, text=True,
                          timeout=600)


def test_dry_mode_runs_every_phase(tmp_path):
    p = _run(["--dry-cpu"], tmp_path)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.splitlines()
    assert lines[0].startswith("DRY MODE") and "proves nothing" in lines[0]
    assert any(ln.startswith("jax ") and "platform=cpu" in ln
               and "device_kind=" in ln and "count=1" in ln for ln in lines)
    for name in ("agree.before.rows_bit_exact", "train.no_compile_in_window",
                 "train.loss_decreased", "agree.after.no_stray_writes",
                 "serve.v1", "serve.v2", "serve.zero_steady_recompiles"):
        assert any(f"ok   {name}" in ln for ln in lines), name
    # a dry run must not print what the driver reads as a chip result
    assert not lines[-1].startswith("{")


def test_no_chip_and_no_dry_option_fails_without_a_result(tmp_path):
    p = _run([], tmp_path)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "platform=cpu" in p.stdout


def test_failed_check_exits_nonzero_naming_the_check(tmp_path):
    # every serve flush raises -> requests come back Failed, not Served
    p = _run(["--dry-cpu"], tmp_path, DETPU_FAULT="raise:serve_step")
    assert p.returncode != 0
    assert "CHECK FAILED [serve.all_served]" in p.stderr
    assert "dry run complete" not in p.stdout


def test_compile_cache_respects_an_outside_setting(monkeypatch):
    import jax

    monkeypatch.setenv(runtime.COMPILE_CACHE_ENV, "/somewhere/else")
    # the key's flags, set by the operator, are the operator's too (and
    # this process's jax config stays as the other tests expect it)
    for flag in runtime.CACHE_KEY_FLAGS:
        monkeypatch.setenv(flag.upper(), "")
    assert runtime.ensure_compile_cache() == "/somewhere/else"
    assert os.environ[runtime.COMPILE_CACHE_ENV] == "/somewhere/else"
    assert not jax.config.jax_compilation_cache_include_metadata_in_key


def test_compile_cache_key_holds_the_scopes(tmp_path):
    """An executable from the cache keeps the metadata it was compiled
    with, so the key holds the metadata (the obs.scope names a profile is
    read by), with the checkout's own path taken off the file names —
    whether jax was imported before the call or after, and wherever the
    operator put the directory."""
    code = ("import os, sys; sys.path.insert(0, %r); %s"
            "from distributed_embeddings_tpu.utils import runtime; "
            "runtime.ensure_compile_cache(); import jax; "
            "print(jax.config.jax_compilation_cache_include_metadata_in_key,"
            " jax.config.jax_hlo_source_file_canonicalization_regex)")
    env = {k: v for k, v in os.environ.items()
           if k.lower() not in runtime.CACHE_KEY_FLAGS}
    env[runtime.COMPILE_CACHE_ENV] = str(tmp_path)
    for early in ("", "import jax; "):
        p = subprocess.run([sys.executable, "-c", code % (REPO, early)],
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        assert p.stdout.split() == ["True", re.escape(REPO + os.sep)]
    regex = runtime.CACHE_KEY_FLAGS[
        "jax_hlo_source_file_canonicalization_regex"]
    assert re.sub(regex, "", os.path.join(
        REPO, "distributed_embeddings_tpu", "parallel", "trainer.py")) \
        == "distributed_embeddings_tpu/parallel/trainer.py"


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    code = ("import os, sys; sys.path.insert(0, %r); "
            "from distributed_embeddings_tpu.utils import runtime; "
            "print(runtime.ensure_compile_cache()); "
            "print(os.environ['JAX_COMPILATION_CACHE_DIR']); "
            "import jax; print(jax.config.jax_compilation_cache_dir)" % REPO)
    env = {k: v for k, v in os.environ.items()
           if k != runtime.COMPILE_CACHE_ENV}
    outs = []
    for cwd in (tmp_path, REPO):
        p = subprocess.run([sys.executable, "-c", code], cwd=str(cwd),
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append(p.stdout.split())
    want = os.path.join(REPO, ".jax_cache")
    assert outs[0] == outs[1] == [want, want, want]


def test_compile_cache_path_has_no_moving_part():
    import inspect

    src = inspect.getsource(runtime.ensure_compile_cache)
    for moving in ("tempfile", "getpid", "time.", "uuid", "random"):
        assert moving not in src, moving
