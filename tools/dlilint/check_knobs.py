"""Knobs checker: code env reads == ``utils/knobs.py`` == docs.

Rules:

- ``knob-unregistered``  — a ``DLI_*`` env read in code with no row in
  ``utils.knobs.KNOBS``.
- ``knob-dead``          — a registry row no code path reads.
- ``knob-undocumented``  — a registry row that never appears in
  ``docs/serving.md``.
- ``knob-doc-dead``      — a ``DLI_*`` token in ``docs/*.md`` that is in
  no registry row (documented knobs must exist).
- ``knob-table-stale``   — the generated table block in serving.md does
  not match ``knobs.generated_block()`` (regenerate with
  ``python -m tools.dlilint --write-knob-table``).

Env reads are found by AST: ``os.environ.get/ setdefault``,
``os.getenv``, ``os.environ[...]`` subscript loads, and calls to local
``_env*`` helper wrappers whose first argument is the var name. A name
given as a bare ``NAME`` is resolved through module-level string
constants. Names starting with ``_DLI`` are internal plumbing (private
env handshakes between a parent and its subprocess) and are exempt.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Tuple

from .core import Ctx, SourceFile, Violation, const_str, dotted_name, \
    filter_suppressed

_KNOB_RE = re.compile(r"^DLI_[A-Z0-9_]+$")
_DOC_TOKEN_RE = re.compile(r"\bDLI_[A-Z0-9_]+\b")

RULES = ("knob-unregistered", "knob-dead", "knob-undocumented",
         "knob-doc-dead", "knob-table-stale")


def _env_read_name(call: ast.Call, consts: Dict[str, str]) -> Optional[str]:
    """The env-var name this Call reads, or None if it isn't a read."""
    fn = call.func
    dn = dotted_name(fn)
    is_env = False
    if dn in ("os.getenv", "getenv"):
        is_env = True
    elif isinstance(fn, ast.Attribute) and fn.attr in ("get", "setdefault"):
        base = dotted_name(fn.value)
        if base in ("os.environ", "environ"):
            is_env = True
    elif isinstance(fn, ast.Name) and fn.id.startswith("_env"):
        # local helper wrappers (e.g. tsdb._env_float) take the var name
        # as their first argument
        is_env = True
    if not is_env or not call.args:
        return None
    return _resolve_name(call.args[0], consts)


def _resolve_name(node: ast.AST, consts: Dict[str, str]) -> Optional[str]:
    s = const_str(node)
    if s is None and isinstance(node, ast.Name):
        s = consts.get(node.id)
    return s


def collect_env_reads(files) -> List[Tuple[SourceFile, int, str]]:
    """(file, line, name) for every DLI_* env read in ``files``."""
    out = []
    for sf in files:
        if sf.tree is None:
            continue
        consts = sf.module_constants()
        for node in ast.walk(sf.tree):
            name = None
            if isinstance(node, ast.Call):
                name = _env_read_name(node, consts)
            elif (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, ast.Load)
                    and dotted_name(node.value) in ("os.environ", "environ")):
                name = _resolve_name(node.slice, consts)
            if name and _KNOB_RE.match(name):
                out.append((sf, node.lineno, name))
    return out


# a shell READ is an expansion — ${DLI_X...} or $DLI_X — never the
# `DLI_X=...` assignment form check.sh uses to arm knobs for child
# processes (those are reads *by the child's python*, counted there)
_SHELL_READ_RE = re.compile(r"\$\{?(DLI_[A-Z0-9_]+)")


def collect_shell_reads(paths) -> List[Tuple[str, int, str]]:
    """(path, line, name) for DLI_* expansions in shell scripts —
    check.sh-only knobs (e.g. DLI_VERIFY_BUDGET) are knobs too and belong
    in the registry + docs like any python-read knob."""
    out = []
    for path in paths:
        with open(path, encoding="utf-8", errors="replace") as f:
            for i, line in enumerate(f, 1):
                for m in _SHELL_READ_RE.finditer(line):
                    out.append((path, i, m.group(1)))
    return out


def check(ctx: Ctx) -> List[Violation]:
    violations: List[Violation] = []
    files = {sf.rel: sf for sf in ctx.package_files + ctx.gate_files}
    registry = ctx.knob_registry or {}

    reads = collect_env_reads(files.values())
    read_names = {}
    for sf, line, name in reads:
        read_names.setdefault(name, (sf.rel, line))
    for path, line, name in collect_shell_reads(ctx.shell_paths):
        rel = path[len(ctx.root) + 1:] if path.startswith(ctx.root) else path
        read_names.setdefault(name, (rel, line))
    # 1. every code read registered
    for name, (rel, line) in sorted(read_names.items()):
        if name not in registry:
            violations.append(Violation(
                "knob-unregistered", rel, line,
                f"env knob {name} read here but missing from "
                f"utils/knobs.py KNOBS"))
    # 2. every registry row read somewhere
    for name in sorted(registry):
        if name not in read_names:
            violations.append(Violation(
                "knob-dead", "distributed_llm_inferencing_tpu/utils/knobs.py",
                1, f"registered knob {name} has no env read in code"))

    # 3./4. docs parity
    serving_text = ""
    if ctx.serving_md:
        with open(ctx.serving_md, encoding="utf-8") as f:
            serving_text = f.read()
        for name in sorted(registry):
            if name not in serving_text:
                violations.append(Violation(
                    "knob-undocumented", "docs/serving.md", 1,
                    f"registered knob {name} missing from the "
                    f"docs/serving.md knob tables"))
    for path in ctx.doc_paths:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        rel = path[len(ctx.root) + 1:] if path.startswith(ctx.root) else path
        for i, line in enumerate(text.splitlines(), 1):
            for tok in _DOC_TOKEN_RE.findall(line):
                if tok not in registry and not tok.startswith("_DLI"):
                    violations.append(Violation(
                        "knob-doc-dead", rel, i,
                        f"doc references {tok}, which is in no "
                        f"utils/knobs.py row (dead documented knob?)"))

    # 5. generated table freshness
    if ctx.serving_md and registry:
        from distributed_llm_inferencing_tpu.utils import knobs as knobs_mod
        if ctx.knob_registry is not None and \
                set(ctx.knob_registry) != set(knobs_mod.registry()):
            pass   # synthetic test registry: freshness check not meaningful
        else:
            block = _extract_block(serving_text, knobs_mod.DOC_BEGIN,
                                   knobs_mod.DOC_END)
            want = knobs_mod.generated_block()
            if block is None:
                violations.append(Violation(
                    "knob-table-stale", "docs/serving.md", 1,
                    "generated knob table markers missing — run "
                    "python -m tools.dlilint --write-knob-table"))
            elif block.strip() != want.strip():
                violations.append(Violation(
                    "knob-table-stale", "docs/serving.md", 1,
                    "generated knob table drifted from utils/knobs.py — "
                    "run python -m tools.dlilint --write-knob-table"))

    return filter_suppressed(violations, files)


def _extract_block(text: str, begin: str, end: str) -> Optional[str]:
    i = text.find(begin)
    j = text.find(end)
    if i < 0 or j < 0:
        return None
    return text[i:j + len(end)]


def write_knob_table(serving_md: str) -> bool:
    """Rewrite (or append) the generated block in ``serving_md``.
    Returns True when the file changed."""
    from distributed_llm_inferencing_tpu.utils import knobs as knobs_mod
    with open(serving_md, encoding="utf-8") as f:
        text = f.read()
    want = knobs_mod.generated_block()
    cur = _extract_block(text, knobs_mod.DOC_BEGIN, knobs_mod.DOC_END)
    if cur is None:
        new = text.rstrip("\n") + "\n\n## Appendix: full knob registry\n\n" \
            + want + "\n"
    elif cur == want:
        return False
    else:
        new = text.replace(cur, want)
    with open(serving_md, "w", encoding="utf-8") as f:
        f.write(new)
    return True
