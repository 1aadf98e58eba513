"""Every ``DETPU_*`` env var read goes through the single registry.

``distributed_embeddings_tpu/utils/envvars.py`` declares every knob (name,
default, meaning). This rule resolves each ``DETPU_*`` env *read* —
``os.environ.get(...)``, ``os.getenv(...)``, ``os.environ[...]``,
``envvars.get/enabled/get_float/get_int(...)`` — to its variable name
(string literals and module-level ``X_ENV = "DETPU_X"`` constants) and
fails on any name the registry does not declare: a typo'd or undeclared
knob ships as a silently-dead env var otherwise. Writes and deletes are
not reads and are ignored.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Optional, Set

from .. import Finding

NAME = "env-registry"
SCOPE = ("distributed_embeddings_tpu/**", "tools/**", "examples/**",
         "__graft_entry__.py", "setup.py")
EXCLUDE = ("distributed_embeddings_tpu/utils/envvars.py",)

REGISTRY_PATH = "distributed_embeddings_tpu/utils/envvars.py"
ENV_READ_HELPERS = {"get", "enabled", "get_float", "get_int"}


def _is_detpu(name: str) -> bool:
    return name.startswith("DETPU_") or name.startswith("_DETPU")


def registered_names(repo: str, ctx: Optional[dict] = None) -> Set[str]:
    """The declared set, extracted from envvars.py's ``declare("...")``
    calls by AST (no import — the registry must be readable by pure
    tooling). Cached per run in ``ctx``."""
    if ctx is not None and "env_registry_names" in ctx:
        return ctx["env_registry_names"]
    names: Set[str] = set()
    path = os.path.join(repo, REGISTRY_PATH)
    try:
        tree = ast.parse(open(path, encoding="utf-8").read(), path)
    except (OSError, SyntaxError):
        tree = None
    if tree is not None:
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "declare"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                names.add(node.args[0].value)
    if ctx is not None:
        ctx["env_registry_names"] = names
    return names


def _module_str_consts(tree: ast.Module) -> Dict[str, str]:
    """Module-level ``NAME = "literal"`` assignments (the ``FAULT_ENV =
    "DETPU_FAULT"`` indirection pattern)."""
    out: Dict[str, str] = {}
    for node in ast.iter_child_nodes(tree):
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out[tgt.id] = node.value.value
    return out


def _resolve(node: ast.AST, consts: Dict[str, str]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def _is_environ(node: ast.AST) -> bool:
    """``os.environ`` (or a bare ``environ`` from ``from os import
    environ``)."""
    if (isinstance(node, ast.Attribute) and node.attr == "environ"
            and isinstance(node.value, ast.Name) and node.value.id == "os"):
        return True
    return isinstance(node, ast.Name) and node.id == "environ"


def check(tree: ast.Module, path: str, src: str, ctx) -> list:
    registry = registered_names(ctx.get("repo", "."), ctx)
    consts = _module_str_consts(tree)
    findings = []

    def flag(node: ast.AST, arg: ast.AST) -> None:
        name = _resolve(arg, consts)
        if name is None or not _is_detpu(name) or name in registry:
            return
        findings.append(Finding(
            NAME, path, node.lineno,
            f"env read of unregistered {name!r} — declare it in "
            f"{REGISTRY_PATH} (default + one-line meaning) so the knob "
            "surface stays discoverable"))

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args:
            f = node.func
            # os.environ.get(...) / environ.get(...)
            if (isinstance(f, ast.Attribute) and f.attr == "get"
                    and _is_environ(f.value)):
                flag(node, node.args[0])
            # os.getenv(...)
            elif (isinstance(f, ast.Attribute) and f.attr == "getenv"
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "os"):
                flag(node, node.args[0])
            # envvars.get/enabled/get_float/get_int(...) — run-time checked
            # too, but catching a typo at lint beats catching it in prod
            elif (isinstance(f, ast.Attribute)
                    and f.attr in ENV_READ_HELPERS
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "envvars"):
                flag(node, node.args[0])
        elif (isinstance(node, ast.Subscript)
                and isinstance(node.ctx, ast.Load)
                and _is_environ(node.value)):
            flag(node, node.slice)
    return findings
