"""Machine-readable name registries: docs tables + AST extractors.

The observability and resilience docs carry the authoritative name
tables — every ``trace_span`` name, monitor gauge, and fault-injection
site.  A table becomes machine-readable by preceding it with an HTML
comment marker::

    <!-- dslint-registry: spans -->
    | span | where |
    |---|---|
    | `serve.tick` | one scheduler tick |
    | `serve/mesh_axis_<axis>` | ... |

The first column's backticked tokens are the registered names; several
names may share a row (```serve.restart` / `serve.replay```).  A name
containing ``<placeholder>`` segments is a *pattern* row matching any
instantiation (``serve/mesh_axis_model``); labeled-gauge rows use the
monitor's ``base{key=<value>}`` form.

The extractors below pull the same names out of the AST so the
registry-conformance rule can prove bidirectional agreement:

- **spans/counters** — the first argument of every ``trace_span(...)``
  / ``trace_count(...)`` call (f-strings become match patterns).
- **gauges** — monitor event names.  The monitor protocol is
  ``write_events([(name, value, step), ...])``; by convention (and now
  by lint) gauge names appear as the literal first element of a 2/3
  tuple, or as keys of a gauge dict (``rollup_host_gauges``).  A
  literal counts as a gauge when its leading ``ns/`` component is one
  of the registry's namespaces — which is what keeps coordination-store
  keys (``fleet/requests/…``) out of the gauge check.
- **fault sites** — ``SITE_* = "…"`` constants in
  ``resilience/fault_injection.py`` plus literal ``maybe_fire``/
  ``fire`` arguments.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from .core import ModuleInfo

__all__ = [
    "RegistryName", "parse_registry", "registry_kinds_in",
    "CodeName", "extract_trace_names", "extract_gauge_names",
    "extract_fault_sites", "extract_tag_names",
]

_MARKER_RE = re.compile(r"<!--\s*dslint-registry:\s*([a-z-]+)\s*-->")
_BACKTICK_RE = re.compile(r"`([^`]+)`")
# a registered name: dotted/slashed identifier, optional {k=v} label
# form, optional <placeholder> segments.  Deliberately loose about
# commas/colons: a malformed name must PARSE so the prom-validity check
# can flag it at its docs line, instead of silently dropping the row
_NAME_RE = re.compile(
    r"^[A-Za-z][A-Za-z0-9_.,:]*(?:/[A-Za-z0-9_.,:<>{}=-]+)*"
    r"(?:\{[A-Za-z0-9_]+=[A-Za-z0-9_<>.-]+\})?$")
_PLACEHOLDER_RE = re.compile(r"<[A-Za-z0-9_.-]+>")


@dataclass(frozen=True)
class RegistryName:
    name: str
    kind: str
    doc_relpath: str
    line: int

    @property
    def is_pattern(self) -> bool:
        return bool(_PLACEHOLDER_RE.search(self.name))

    def regex(self) -> re.Pattern:
        """Pattern rows: each ``<placeholder>`` matches one freeform
        segment (no ``/`` or ``{`` — a placeholder never spans
        components)."""
        parts: List[str] = []
        pos = 0
        for m in _PLACEHOLDER_RE.finditer(self.name):
            parts.append(re.escape(self.name[pos:m.start()]))
            parts.append(r"[A-Za-z0-9_.:-]+")
            pos = m.end()
        parts.append(re.escape(self.name[pos:]))
        return re.compile("^" + "".join(parts) + "$")

    def matches(self, name: str) -> bool:
        if not self.is_pattern:
            return name == self.name
        return bool(self.regex().match(name))


def parse_registry(md_text: str, doc_relpath: str,
                   kind: str) -> List[RegistryName]:
    """All names in ``kind``-marked tables of one markdown document.
    A marker binds to the next table (first column only); multiple
    marked tables of the same kind concatenate."""
    out: List[RegistryName] = []
    lines = md_text.splitlines()
    i = 0
    while i < len(lines):
        m = _MARKER_RE.search(lines[i])
        if not m or m.group(1) != kind:
            i += 1
            continue
        # find the table: first subsequent line starting with '|'
        j = i + 1
        while j < len(lines) and not lines[j].lstrip().startswith("|"):
            if _MARKER_RE.search(lines[j]):   # next marker before a table
                break
            j += 1
        # walk the table rows; skip header + |---| separator
        header_seen = 0
        while j < len(lines) and lines[j].lstrip().startswith("|"):
            row = lines[j]
            if header_seen < 2:
                header_seen += 1
                if re.match(r"^\s*\|[\s:|-]+\|\s*$", row):
                    j += 1
                    continue
                if header_seen == 1:
                    j += 1
                    continue
            first_cell = row.split("|")[1] if row.count("|") >= 2 else ""
            for tok in _BACKTICK_RE.findall(first_cell):
                tok = tok.strip()
                if _NAME_RE.match(tok):
                    out.append(RegistryName(name=tok, kind=kind,
                                            doc_relpath=doc_relpath,
                                            line=j + 1))
            j += 1
        i = j
    return out


def registry_kinds_in(md_text: str) -> List[str]:
    return [m.group(1) for m in _MARKER_RE.finditer(md_text)]


# ------------------------------------------------------------ extraction

@dataclass(frozen=True)
class CodeName:
    """A name (or f-string pattern) the code emits."""

    name: str            # literal text; f-string parts joined with \x00
    relpath: str
    line: int
    dynamic: bool        # True when built from an f-string

    def matches_registry(self, row: RegistryName) -> bool:
        if not self.dynamic:
            return row.matches(self.name)
        # dynamic name: constant fragments with wildcard gaps — match a
        # registry row iff the row (pattern or literal) could produce
        # the same shape: compare by regex over the row's NAME using the
        # code side as the pattern.
        parts = [re.escape(p) for p in self.name.split("\x00")]
        rx = re.compile("^" + "[A-Za-z0-9_.:<>-]+".join(parts) + "$")
        return bool(rx.match(row.name))


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _joined_pattern(node: ast.AST) -> Optional[str]:
    """f-string -> constant fragments joined by NUL (wildcard gaps)."""
    if not isinstance(node, ast.JoinedStr):
        return None
    parts: List[str] = [""]
    for v in node.values:
        s = _const_str(v)
        if s is not None:
            parts[-1] += s
        else:
            parts.append("")
    return "\x00".join(parts)


def _name_of_call(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def extract_trace_names(modules: Sequence[ModuleInfo],
                        funcs: Tuple[str, ...] = ("trace_span",
                                                  "trace_count"),
                        ) -> Dict[str, List[CodeName]]:
    """``{"trace_span": [...], "trace_count": [...]}`` — the first
    argument of every call to the tracer entry points."""
    out: Dict[str, List[CodeName]] = {f: [] for f in funcs}
    for mod in modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fname = _name_of_call(node)
            if fname not in funcs:
                continue
            arg = node.args[0]
            s = _const_str(arg)
            if s is not None:
                out[fname].append(CodeName(s, mod.relpath, node.lineno,
                                           dynamic=False))
                continue
            p = _joined_pattern(arg)
            if p is not None:
                out[fname].append(CodeName(p, mod.relpath, node.lineno,
                                           dynamic=True))
    return out


def _gauge_candidate(text: str, namespaces: Sequence[str]) -> bool:
    head = text.split("/", 1)[0].split("{", 1)[0]
    return ("/" in text or "{" in text) and head in namespaces


def extract_gauge_names(modules: Sequence[ModuleInfo],
                        namespaces: Sequence[str]
                        ) -> List[CodeName]:
    """Monitor gauge names: literal (or f-string) first elements of 2/3
    tuples, plus string dict keys — filtered to the registry's
    namespaces so store keys and log strings never enter the check."""
    out: List[CodeName] = []
    for mod in modules:
        for node in ast.walk(mod.tree):
            heads: List[ast.AST] = []
            if isinstance(node, ast.Tuple) and len(node.elts) in (2, 3):
                heads = [node.elts[0]]
            elif isinstance(node, ast.Dict):
                heads = [k for k in node.keys if k is not None]
            for h in heads:
                s = _const_str(h)
                if s is not None:
                    if _gauge_candidate(s, namespaces):
                        out.append(CodeName(s, mod.relpath, h.lineno,
                                            dynamic=False))
                    continue
                p = _joined_pattern(h)
                if p is not None and _gauge_candidate(
                        p.replace("\x00", "X"), namespaces):
                    out.append(CodeName(p, mod.relpath, h.lineno,
                                        dynamic=True))
    return out


def extract_tag_names(modules: Sequence[ModuleInfo],
                      funcs: Tuple[str, ...] = ("trace_context",
                                                "trace_tags"),
                      ) -> List[CodeName]:
    """Trace-context TAG keys (docs/OBSERVABILITY.md "Distributed
    tracing"): the keyword names of every ``trace_context(...)`` /
    ``trace_tags(...)`` call, plus the implicit ``trace_id``/``rid`` keys
    a ``trace_context`` with positional identity arguments injects, plus
    mid-span attrs set through ``<span>.set(key=...)`` (the slot→rid map
    rides that path) or built by a ``*_attrs`` function a span is given
    whole.  Keyword'd ``.set`` calls are matched by method
    name — in this tree only span contexts take keyword ``set`` args, and
    a future non-span hit just prompts a registry row or a rename.  Tag
    keys become Perfetto ``args`` keys and fleet-trace filter terms — the
    registry table is the operator contract for what can be filtered on."""
    out: List[CodeName] = []
    for mod in modules:
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.FunctionDef)
                    and node.name.endswith("_attrs")):
                # a function that builds the attrs a span is then given
                # whole (``sp.set(**layout.decode_attrs(...))``): the string
                # keys of its dict literals, the keywords of its ``update``s
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Dict):
                        keys = [_const_str(k) for k in sub.keys]
                    elif (isinstance(sub, ast.Call)
                            and _name_of_call(sub) == "update"):
                        keys = [kw.arg for kw in sub.keywords]
                    else:
                        continue
                    out.extend(CodeName(k, mod.relpath, sub.lineno,
                                        dynamic=False) for k in keys if k)
                continue
            if not isinstance(node, ast.Call):
                continue
            fname = _name_of_call(node)
            if fname == "set" and isinstance(node.func, ast.Attribute):
                for kw in node.keywords:
                    if kw.arg is not None:
                        out.append(CodeName(kw.arg, mod.relpath,
                                            node.lineno, dynamic=False))
                continue
            if fname not in funcs:
                continue
            names = [kw.arg for kw in node.keywords if kw.arg is not None]
            if fname == "trace_context":
                # positional trace_id/rid inject those keys implicitly;
                # count them only when actually passed (non-None spelling
                # is a runtime property — registering the pair whenever a
                # positional arg appears keeps the check sound)
                if len(node.args) >= 1:
                    names.append("trace_id")
                if len(node.args) >= 2:
                    names.append("rid")
            for n in names:
                out.append(CodeName(n, mod.relpath, node.lineno,
                                    dynamic=False))
    return out


def extract_fault_sites(modules: Sequence[ModuleInfo],
                        const_prefix: str = "SITE_",
                        fire_funcs: Tuple[str, ...] = ("maybe_fire",
                                                       "fire"),
                        ) -> List[CodeName]:
    """Fault-site strings: ``SITE_* = "…"`` constants (the canonical
    spellings in resilience/fault_injection.py) plus any literal site
    passed straight to ``maybe_fire``/``FaultInjector.fire``."""
    out: List[CodeName] = []
    for mod in modules:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign):
                s = _const_str(node.value)
                if s is None:
                    continue
                for t in node.targets:
                    if (isinstance(t, ast.Name)
                            and t.id.startswith(const_prefix)
                            and t.id != const_prefix.rstrip("_") + "S"):
                        out.append(CodeName(s, mod.relpath, node.lineno,
                                            dynamic=False))
            elif isinstance(node, ast.Call) and node.args:
                if _name_of_call(node) in fire_funcs:
                    s = _const_str(node.args[0])
                    if s is not None:
                        out.append(CodeName(s, mod.relpath, node.lineno,
                                            dynamic=False))
    return out
