"""No module-scope jax import in host-side infrastructure modules.

``utils.runtime`` / ``utils.obs`` / ``utils.envvars`` hold the
never-touch-a-backend-at-import contract: their counter/registry halves
must work in processes that never load jax at all, and importing them must
never risk initializing an accelerator backend. detlint itself promises
the same ("runs anywhere, instantly"). This rule pins the contract: any
module-scope ``import jax`` / ``from jax... import`` in a scoped file is
a finding — import it inside the function that needs it.
"""

from __future__ import annotations

import ast

from .. import Finding

NAME = "module-scope-jax"
SCOPE = ("distributed_embeddings_tpu/utils/obs.py",
         "distributed_embeddings_tpu/utils/runtime.py",
         "distributed_embeddings_tpu/utils/envvars.py",
         "distributed_embeddings_tpu/utils/traceparse.py",
         "tools/detlint/**")


def check(tree: ast.Module, path: str, src: str, ctx) -> list:
    findings = []
    for node in ast.iter_child_nodes(tree):
        names = []
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names = [node.module or ""]
        if any(n == "jax" or n.startswith("jax.") for n in names):
            findings.append(Finding(
                NAME, path, node.lineno,
                "module-scope jax import — this module must stay "
                "importable without jax (the runtime-layer contract); "
                "import it inside the function that needs it"))
    return findings
