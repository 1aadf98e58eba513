"""The tree's records agree with the tree.

What a reader is sent to exists (documents, the Makefile), what the
registry declares is read, the package leans on no script around it, and
the two published vocabulary vectors have one source. Pure file reading:
no jax, nothing of the package is imported.
"""

import ast
import functools
import glob
import importlib.util
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "distributed_embeddings_tpu")

DOCUMENTS = ("README.md", "docs/userguide.md", "docs/api.md",
             "examples/dlrm/README.md")

#: names a document gives to files that a RUN writes or a dataset brings
#: (checkpoint manifests, sidecars), not to files of the tree
RUN_ARTIFACTS = {"meta.json", "model_size.json", "metrics.jsonl"}


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


def _load(rel, name):
    """A module of the tree by path, so that importing it imports nothing
    of the package around it."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _tree_basenames():
    """Names of the files git would see: no hidden directory, no cache, no
    copy of another commit under ``chiprun_work/``."""
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "chiprun_"))
                   and d != "__pycache__"]
        names.update(files)
    return names


def _python_files(*roots):
    for root in roots:
        path = os.path.join(REPO, root)
        if os.path.isfile(path):
            yield root
            continue
        for d, dirs, files in os.walk(path):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                if f.endswith(".py"):
                    yield os.path.relpath(os.path.join(d, f), REPO)


# ------------------------------------------------------------ the Makefile


def _makefile():
    """``{target: (prerequisites, recipe lines)}`` of the root Makefile."""
    text = _read("Makefile").replace("\\\n", " ")
    rules, current = {}, None
    for line in text.split("\n"):
        m = re.match(r"^([A-Za-z][\w-]*):(?!=)\s*(.*)$", line)
        if m:
            current = m.group(1)
            rules[current] = (m.group(2).split(), [])
        elif line.startswith("\t") and current:
            rules[current][1].append(line.strip())
        elif line.strip() and not line.startswith("#"):
            current = None
    return rules


def test_makefile_recipes_run_only_files_in_the_tree():
    missing = []
    for target, (_, recipe) in _makefile().items():
        for line in recipe:
            for path in re.findall(r"(?<![\w/.-])([\w./-]+\.py)\b", line):
                if not os.path.exists(os.path.join(REPO, path)):
                    missing.append(f"{target}: {path}")
            for mod in re.findall(r"python3? -m ((?:tools|benchmarks)[\w.]*)",
                                  line):
                rel = mod.replace(".", "/")
                if not (os.path.exists(os.path.join(REPO, rel + ".py"))
                        or os.path.isdir(os.path.join(REPO, rel))):
                    missing.append(f"{target}: -m {mod}")
    assert not missing, missing


def test_verify_prerequisites_are_targets():
    rules = _makefile()
    prerequisites, _ = rules["verify"]
    assert prerequisites, "make verify runs no gate before the tests"
    assert [p for p in prerequisites if p not in rules] == []
    phony = re.search(r"^\.PHONY:(.*)$",
                      _read("Makefile").replace("\\\n", " "), re.M)
    assert [p for p in phony.group(1).split() if p not in rules] == []


# ------------------------------------------------------------ the documents


def _exists(path, doc):
    """Whether a path a document names is in the tree: from the root, from
    the package, or beside the document; a bare ``.py`` or ``.md`` name
    anywhere."""
    bases = [REPO, PACKAGE, os.path.dirname(os.path.join(REPO, doc))]
    # `docs/{userguide,api}.md`: every alternative must be there
    m = re.match(r"^(.*)\{([^{}]+)\}(.*)$", path)
    if m:
        return all(_exists(m.group(1) + alt + m.group(3), doc)
                   for alt in m.group(2).split(","))
    if any(glob.glob(os.path.join(b, path)) for b in bases):
        return True
    if "/" not in path and path.endswith((".py", ".md")):
        return path in _tree_basenames()
    return False


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_documents_name_only_files_in_the_tree(doc):
    text = _read(doc)
    targets = set(_makefile())
    missing = []
    for m in re.finditer(r"`([^`\n]+)`( there\b)?", text):
        if m.group(2):  # "`path` there": a file of the reference's tree
            continue
        quoted = m.group(1)
        for target in re.findall(r"\bmake ([a-z][\w-]*)", quoted):
            if target not in targets:
                missing.append(f"make {target}")
        for word in quoted.split():
            word = word.strip("(),;")
            word = re.sub(r"(::|:)[\w\-,.:\[\]]*$", "", word)  # :12, ::test
            if not re.search(r"\.(py|md|json|jsonl)$", word):
                continue
            if "<" in word or word in RUN_ARTIFACTS:
                continue
            if not _exists(word, doc):
                missing.append(word)
    assert not missing, sorted(set(missing))


# ---------------------------------------------------- the registry of names

ENVVARS = "distributed_embeddings_tpu/utils/envvars.py"

#: registered names that nothing reads, older than the reader test: named
#: debts of ROADMAP.md, Design 8 (a PR that deletes one deletes its entry)
UNREAD_DEBTS = frozenset()


def test_every_registered_env_name_has_a_reader():
    names = set(_load(ENVVARS, "_envvars_by_path").registered())
    readers = [f for f in _python_files(
        "distributed_embeddings_tpu", "tools", "examples", "benchmarks",
        "chip_smoke.py", "__graft_entry__.py", "setup.py")
        if f != ENVVARS]
    text = "\n".join(_read(f) for f in readers)
    read = set(re.findall(r"\b_?DETPU_[A-Z0-9_]+\b", text))
    unread = names - read
    assert unread == set(UNREAD_DEBTS), (
        f"registered and never read: {sorted(unread - UNREAD_DEBTS)}; "
        f"listed as unread but read (or gone): "
        f"{sorted(UNREAD_DEBTS - unread)}")


# ------------------------------------------ the package and what is around it


def _imported_modules(rel):
    tree = ast.parse(_read(rel), filename=rel)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, node.lineno
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            yield mod, node.lineno
            if mod == "tools":  # from tools import x
                for a in node.names:
                    yield f"tools.{a.name}", node.lineno


def test_package_imports_no_script_around_it():
    """The program, its examples and the smoke test lean on no benchmark and
    on no tool but the shared shapes. (The supervisor's worker factory is a
    string, not an import: ROADMAP.md, Design, layering.)"""
    bad = []
    for rel in _python_files("distributed_embeddings_tpu", "examples",
                             "chip_smoke.py"):
        for mod, line in _imported_modules(rel):
            top = mod.split(".")[0]
            if top in ("bench", "benchmarks"):
                bad.append(f"{rel}:{line}: {mod}")
            elif top == "tools" and mod not in ("tools",
                                                "tools._profcommon"):
                bad.append(f"{rel}:{line}: {mod}")
    assert not bad, bad


# ------------------------------------------------- the vocabulary vectors


@pytest.mark.parametrize("config,vector", [
    ("dlrm-kaggle", "CRITEO_KAGGLE_SIZES"),
    ("dlrm-criteo1tb", "CRITEO_1TB_SIZES"),
])
def test_vocabulary_sizes_have_one_source(config, vector):
    """The smoke test, the auditors and the benchmark's cells price the
    same vectors."""
    shapes = _load("tools/_profcommon.py", "_profcommon_by_path")
    with open(os.path.join(REPO, "benchmarks", "configs", config + ".json"),
              encoding="utf-8") as f:
        published = json.load(f)["table_sizes"]
    assert list(getattr(shapes, vector)) == published
