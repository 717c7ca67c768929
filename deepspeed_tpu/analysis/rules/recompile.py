"""recompile-hazard: jit creation outside the approved seams, and
shape-baking coercions inside traced program bodies.

The zero-recompile contract (docs/SERVING.md, PAPER.md §L1's fused-
kernel discipline, here "sharding is placement, never a program shape")
rests on every ``jax.jit`` living at one of three kinds of seam:

- **module-level process-global jits** — ``_COW_PROG``-style jits
  that every engine incarnation shares (a warm restart must hit the jit
  cache, not recompile inside the recovery critical path — the exact
  bug PR 6's review caught by hand);
- **the MeshExecutor program builders** (``inference/execution.py``) —
  the ONE place serving programs are created, behind ``pool_jit``;
- **the engine gen-cache** (``InferenceEngine._cached_program``
  builders) — bounded, keyed, shared across calls.

A jit created in ``__init__`` or any other per-instance scope gets a
fresh cache per object: the first engine pays a compile, and so does
every replacement after a fault — on a real slice that is a multi-
second decode stall that CPU tier-1 never sees.

The second half flags Python coercions of traced values — ``int()``,
``float()``, ``bool()``, ``.item()``, ``.tolist()``, ``np.asarray`` —
*inside functions that are jit-compiled* (decorated, passed to
``jax.jit``/``pool_jit`` in the same module, or jitted lambdas).  Under
trace these either raise ``ConcretizationTypeError`` at runtime or, for
shape-deriving uses, silently bake a Python value into the program so
the "one program for all param mixes" inventory quietly forks.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Sequence, Set, Tuple

from ..core import Finding, ModuleInfo, Rule
from ._util import dotted_name, enclosing_function, qualname, walk_scoped

# functions whose call creates a jit cache
_JIT_MAKERS = {"jax.jit", "jit", "pjit", "jax.experimental.pjit.pjit"}
# wrappers that forward to jax.jit and are themselves approved seams —
# a function *passed into* one of these is a traced body
_JIT_WRAPPERS = {"pool_jit"}
_COERCIONS = {"int", "float", "bool"}
_COERCION_ATTRS = {"item", "tolist"}
_COERCION_CALLS = {"np.asarray", "np.array", "numpy.asarray",
                   "numpy.array"}

# (relpath, qualname-prefix): jit creation allowed here.  "" = whole
# file.  These are the repo's three sanctioned seam kinds made concrete;
# everything else needs an inline suppression with a reviewed reason or
# a baseline entry (docs/ANALYSIS.md "recompile-hazard").
DEFAULT_APPROVED_SEAMS: Tuple[Tuple[str, str], ...] = (
    ("deepspeed_tpu/inference/execution.py", ""),
    # gen-cache builders: only ever invoked through _cached_program's
    # bounded OrderedDict keyed on (model identity, shape tail), so the
    # jit they return is cached-and-shared, not per-call
    ("deepspeed_tpu/inference/engine.py",
     "InferenceEngine._generate_program"),
    ("deepspeed_tpu/inference/engine.py",
     "InferenceEngine._generate_lanes_program"),
    # the train engine compiles its fused step/grad programs once at
    # construction by design (one training engine per process; the
    # serving zero-recompile contract does not cover the train path)
    ("deepspeed_tpu/runtime/engine.py", ""),
)


class RecompileHazardRule(Rule):
    id = "recompile-hazard"
    description = ("jax.jit/pjit outside the approved program seams, or "
                   "a traced-value coercion inside a jitted body")

    def __init__(self, approved_seams: Sequence[Tuple[str, str]]
                 = DEFAULT_APPROVED_SEAMS):
        self.approved_seams = tuple(approved_seams)

    # ------------------------------------------------------------ helpers

    def _approved(self, relpath: str, qname: str) -> bool:
        for path, prefix in self.approved_seams:
            if relpath == path and (prefix == "" or qname == prefix
                                    or qname.startswith(prefix + ".")):
                return True
        return False

    @staticmethod
    def _is_jit_call(node: ast.Call) -> bool:
        name = dotted_name(node.func)
        return name is not None and (
            name in _JIT_MAKERS or name.endswith(".pjit"))

    # ------------------------------------------------------------- checks

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        findings: List[Finding] = []
        jitted_bodies: List[Tuple[ast.AST, str]] = []  # (body node, label)
        # names (possibly dotted) passed as the first arg to a jit maker
        # or wrapper in this module -> the traced function names
        traced_names: Set[str] = set()

        for node, scopes in walk_scoped(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            is_maker = self._is_jit_call(node)
            is_wrapper = callee in _JIT_WRAPPERS if callee else False
            if not (is_maker or is_wrapper):
                continue
            if node.args:
                first = node.args[0]
                fn_name = dotted_name(first)
                if fn_name is not None:
                    traced_names.add(fn_name.split(".")[-1])
                elif isinstance(first, ast.Lambda):
                    jitted_bodies.append((first, "<lambda>"))
            if not is_maker:
                continue
            qname = qualname(scopes)
            fn = enclosing_function(scopes)
            if fn is None:
                continue   # module level: process-global by construction
            if self._approved(mod.relpath, qname):
                continue
            where = ("__init__ (per-instance: every object gets a fresh "
                     "jit cache, every replacement recompiles)"
                     if fn == "__init__" else f"per-instance scope "
                     f"'{qname}'")
            findings.append(Finding(
                rule=self.id, path=mod.relpath, line=node.lineno,
                message=(f"jit created in {where} — move it to a "
                         "module-level process-global cache or an "
                         "approved seam (docs/ANALYSIS.md)"),
                key=f"jit@{qname}"))

        # second pass: find decorated / referenced traced bodies
        for node, scopes in walk_scoped(mod.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            label = qualname(scopes + (("func", node.name),))
            for dec in node.decorator_list:
                dname = dotted_name(dec.func if isinstance(dec, ast.Call)
                                    else dec)
                if dname and (dname in _JIT_MAKERS
                              or dname.endswith(".jit")):
                    jitted_bodies.append((node, label))
                    break
            else:
                if node.name in traced_names:
                    jitted_bodies.append((node, label))

        for body, label in jitted_bodies:
            findings.extend(self._check_traced_body(mod, body, label))
        return findings

    def _check_traced_body(self, mod: ModuleInfo, body: ast.AST,
                           label: str) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(body):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            bad: Optional[str] = None
            if callee in _COERCIONS and node.args \
                    and not isinstance(node.args[0], ast.Constant):
                bad = f"{callee}()"
            elif callee in _COERCION_CALLS:
                bad = callee
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _COERCION_ATTRS:
                bad = f".{node.func.attr}()"
            if bad is not None:
                out.append(Finding(
                    rule=self.id, path=mod.relpath, line=node.lineno,
                    message=(f"{bad} on a traced value inside jitted "
                             f"body '{label}' — bakes a Python value "
                             "into the program (shape fork) or raises "
                             "under trace"),
                    key=f"coerce:{bad}@{label}"))
        return out
