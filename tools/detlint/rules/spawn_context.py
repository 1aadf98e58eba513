"""multiprocessing must request the ``spawn`` start method explicitly.

The default start method on Linux is ``fork``, and forking a process
that has initialised the jax backend deadlocks: XLA's runtime threads
and locks are duplicated mid-state into a child that will never run
them (the supervisor's worker processes exist precisely because of
this). Package code therefore never uses the default context:

* ``from multiprocessing import Process/Pool/Manager`` (or the
  ``multiprocessing.pool`` / ``multiprocessing.managers`` modules)
  binds the DEFAULT context — a finding at the import;
* ``<mp>.Process(...)`` / ``<mp>.Pool(...)`` / ``<mp>.Manager(...)``
  on the raw module is the same thing at the call site;
* ``get_context()`` / ``get_context("fork")`` / ``set_start_method``
  with anything but the literal ``"spawn"`` asks for the hazard by
  name.

The blessed idiom is ``parallel/supervisor.py``'s module policy::

    _SPAWN = multiprocessing.get_context("spawn")
    ...
    _SPAWN.Process(target=_worker_main, args=(spec,))

Process-free corners of the package (``multiprocessing.shared_memory``,
``.connection``, ``.resource_tracker``) start nothing and stay quiet.
A site that genuinely needs fork (no jax in the process, ever)
annotates ``# spawn-ok: <reason>`` on the line.
"""

from __future__ import annotations

import ast

from .. import Finding

NAME = "spawn-context"
SCOPE = ("distributed_embeddings_tpu/**", "tools/**",
         "__graft_entry__.py")

MARKER = "spawn-ok:"

#: names that bind the default (fork) context when taken off the raw
#: module or imported directly
DEFAULT_CTX_FACTORIES = {"Process", "Pool", "Manager"}
#: submodules that are nothing but default-context factories
DEFAULT_CTX_MODULES = {"multiprocessing.pool", "multiprocessing.managers"}


def _first_arg_literal(call: ast.Call):
    if call.args and isinstance(call.args[0], ast.Constant):
        return call.args[0].value
    for kw in call.keywords:
        if kw.arg == "method" and isinstance(kw.value, ast.Constant):
            return kw.value.value
    return None


def check(tree: ast.Module, path: str, src: str, ctx) -> list:
    lines = src.splitlines()
    findings = []
    mp_aliases = set()      # names bound to the multiprocessing module
    ctx_getters = set()     # bare names bound to get_context/set_start_method

    def _waived(lineno: int) -> bool:
        return MARKER in lines[lineno - 1]

    def _finding(lineno: int, what: str):
        if not _waived(lineno):
            findings.append(Finding(
                NAME, path, lineno,
                f"{what} uses the default (fork) start method — fork "
                "after jax backend init deadlocks; request spawn "
                'explicitly (multiprocessing.get_context("spawn"), the '
                "supervisor's _SPAWN idiom) or annotate "
                f"'# {MARKER} <reason>'"))

    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "multiprocessing":
                    mp_aliases.add(a.asname or a.name)
                elif a.name in DEFAULT_CTX_MODULES:
                    _finding(node.lineno, f"import {a.name}")
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            if mod == "multiprocessing":
                for a in node.names:
                    if a.name in DEFAULT_CTX_FACTORIES:
                        _finding(node.lineno,
                                 f"from multiprocessing import {a.name}")
                    elif a.name in ("get_context", "set_start_method"):
                        ctx_getters.add(a.asname or a.name)
            elif mod in DEFAULT_CTX_MODULES:
                _finding(node.lineno, f"from {mod} import ...")

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id in mp_aliases):
            if f.attr in DEFAULT_CTX_FACTORIES:
                _finding(node.lineno, f"{f.value.id}.{f.attr}()")
            elif f.attr in ("get_context", "set_start_method"):
                if _first_arg_literal(node) != "spawn":
                    _finding(node.lineno,
                             f"{f.value.id}.{f.attr}(...) without the "
                             'literal "spawn"')
        elif isinstance(f, ast.Name) and f.id in ctx_getters:
            if _first_arg_literal(node) != "spawn":
                _finding(node.lineno,
                         f'{f.id}(...) without the literal "spawn"')
    findings.sort(key=lambda x: x.line)
    return findings
