"""detlint — the repo's pluggable AST lint framework.

One rule framework in place of ad-hoc static checkers that each
reimplemented file walking and reporting:

* a **rule** is a module in :mod:`tools.detlint.rules` exposing ``NAME``
  (kebab-case id), ``SCOPE`` (repo-relative glob patterns of the files it
  applies to), optional ``EXCLUDE`` globs, and
  ``check(tree, path, src, ctx) -> [Finding]`` where ``tree`` is the
  parsed ``ast`` module, ``path`` the repo-relative posix path, ``src``
  the file text, and ``ctx`` a per-run scratch dict (rules cache things
  like the env-var registry there);
* the runner walks the repo once, parses each file once, and hands every
  rule the files its scope matches;
* ``python -m tools.detlint`` (wired as ``make lint``) prints findings as
  ``detlint: <path>:<line>: [<rule>] <message>`` and exits nonzero when
  anything fired.

Pure stdlib + AST: no jax import, runs anywhere, instantly.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import importlib
import json
import os
import pkgutil
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: directories never walked (build junk, vendored native code, VCS)
SKIP_DIRS = {".git", "__pycache__", "build", "dist", ".claude", "cc",
             ".pytest_cache"}


@dataclasses.dataclass
class Finding:
    """One lint finding, pointing at a repo-relative line."""
    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def discover_rules() -> Dict[str, Any]:
    """Import every module in :mod:`tools.detlint.rules` exposing a
    ``NAME`` + ``check`` pair — dropping a new rule module in the package
    is the whole registration story."""
    from . import rules as rules_pkg

    out: Dict[str, Any] = {}
    for info in pkgutil.iter_modules(rules_pkg.__path__):
        if info.name.startswith("_"):
            continue
        mod = importlib.import_module(f"{rules_pkg.__name__}.{info.name}")
        name = getattr(mod, "NAME", None)
        if name and callable(getattr(mod, "check", None)):
            out[name] = mod
    return out


def iter_py_files(repo: str = REPO) -> Iterable[str]:
    """Every checkable ``*.py`` as a repo-relative posix path."""
    for base, dirs, files in os.walk(repo):
        dirs[:] = sorted(d for d in dirs
                         if d not in SKIP_DIRS and not d.endswith(".egg-info"))
        for f in sorted(files):
            if f.endswith(".py"):
                rel = os.path.relpath(os.path.join(base, f), repo)
                yield rel.replace(os.sep, "/")


_glob_cache: Dict[str, "re.Pattern[str]"] = {}


def _compile_glob(pat: str) -> "re.Pattern[str]":
    """Path-aware glob -> regex: ``*``/``?`` stay within one path segment
    (fnmatch's ``*`` crosses ``/``, which makes scopes mean more than they
    read); ``**`` crosses segments."""
    rx = _glob_cache.get(pat)
    if rx is None:
        parts, i = [], 0
        while i < len(pat):
            if pat.startswith("**", i):
                parts.append(".*")
                i += 2
            elif pat[i] == "*":
                parts.append("[^/]*")
                i += 1
            elif pat[i] == "?":
                parts.append("[^/]")
                i += 1
            else:
                parts.append(re.escape(pat[i]))
                i += 1
        rx = _glob_cache[pat] = re.compile("^" + "".join(parts) + "$")
    return rx


def _matches(path: str, patterns: Sequence[str]) -> bool:
    return any(_compile_glob(p).match(path) for p in patterns)


def run(repo: str = REPO,
        rule_names: Optional[Sequence[str]] = None,
        files: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run rules over the repo (or an explicit file list); returns every
    finding. Unknown rule names raise — a gate that silently skips a
    misspelled rule is worse than no gate."""
    rules = discover_rules()
    if rule_names:
        unknown = sorted(set(rule_names) - set(rules))
        if unknown:
            raise ValueError(f"unknown detlint rule(s): {', '.join(unknown)} "
                             f"(have: {', '.join(sorted(rules))})")
        rules = {k: rules[k] for k in rule_names}

    if files is not None:
        # normalize explicit args (absolute, ./-prefixed, OS separators) to
        # repo-relative posix form — SCOPE globs only speak that dialect,
        # and an unmatchable path would silently lint as "clean"
        paths = []
        for f in files:
            if not os.path.isabs(f) and os.path.exists(os.path.join(repo, f)):
                rel = f  # already repo-relative
            else:
                rel = os.path.relpath(os.path.abspath(f), repo)
            if rel.startswith(".."):
                raise ValueError(f"{f!r} lies outside the repo {repo!r}")
            paths.append(rel.replace(os.sep, "/"))
    else:
        paths = list(iter_py_files(repo))
    ctx: Dict[str, Any] = {"repo": repo}
    findings: List[Finding] = []
    for rel in paths:
        full = os.path.join(repo, rel)
        applicable = [m for m in rules.values()
                      if _matches(rel, getattr(m, "SCOPE", ("**",)))
                      and not _matches(rel, getattr(m, "EXCLUDE", ()))]
        if not applicable:
            continue
        try:
            with open(full, encoding="utf-8") as fh:
                src = fh.read()
            tree = ast.parse(src, filename=rel)
        except (OSError, SyntaxError) as e:
            findings.append(Finding("parse", rel, getattr(e, "lineno", 0) or 0,
                                    f"unparseable: {e}"))
            continue
        for mod in applicable:
            findings.extend(mod.check(tree, rel, src, ctx))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="detlint", description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*",
                    help="repo-relative files to check (default: whole repo)")
    ap.add_argument("--rule", action="append", dest="rules", metavar="NAME",
                    help="run only this rule (repeatable)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="emit findings as a JSON array")
    args = ap.parse_args(argv)

    if args.list_rules:
        for name, mod in sorted(discover_rules().items()):
            doc = (mod.__doc__ or "").strip().splitlines()
            print(f"{name}: {doc[0] if doc else ''}")
        return 0

    try:
        findings = run(rule_names=args.rules, files=args.files or None)
    except ValueError as e:
        print(f"detlint: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps([f.to_json() for f in findings], indent=2))
    else:
        for f in findings:
            print(f"detlint: {f}", file=sys.stderr)
    if findings:
        print(f"detlint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    n_rules = len(args.rules) if args.rules else len(discover_rules())
    print(f"detlint: OK ({n_rules} rule(s), no findings)")
    return 0
