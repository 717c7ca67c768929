"""host-sync: device work hiding in the host-scheduling paths.

PR 10 split serving into host scheduling (pure Python + numpy over page
tables) and a MeshExecutor owning every device array.  The split is
what makes the tick loop's latency predictable: admission, routing,
deadline math and gauge writes never wait on a device.  One stray
``jnp.*`` call — or an implicit materialization like ``.item()`` /
``jax.device_get`` / ``block_until_ready`` — in those paths re-couples
the scheduler to device completion: a hidden sync that stalls every
slot's tick behind whatever the device happens to be running (and on a
mesh, behind the slowest shard).

The rule designates host-only scopes and flags device-touching
expressions inside them:

- whole modules that must never touch a device (``fleet.py`` routes and
  journals, ``serving_supervisor.py`` replays through engine entry
  points);
- named host-path methods of ``ServingEngine`` — the admission /
  routing / accounting half (the prefill/decode halves live behind
  ``self._exec`` and are exempt by construction).

``np.asarray`` is deliberately NOT flagged: on host lists it is the
idiom (page tables are numpy).  The materializing spellings a device
array can reach these scopes through — ``jnp.*``, ``jax.device_get``,
``.item()``, ``.block_until_ready()``, ``jax.block_until_ready`` — are.
"""
from __future__ import annotations

import ast
from typing import List, Mapping, Sequence, Tuple

from ..core import Finding, ModuleInfo, Rule
from ._util import dotted_name, qualname, walk_scoped

DEFAULT_HOST_MODULES: Tuple[str, ...] = (
    "deepspeed_tpu/inference/fleet.py",
    "deepspeed_tpu/inference/page_pool.py",
    "deepspeed_tpu/inference/serving_supervisor.py",
)

# per-module host-only function scopes (qualname prefixes)
DEFAULT_HOST_FUNCTIONS: Mapping[str, Tuple[str, ...]] = {
    "deepspeed_tpu/inference/serving.py": (
        "ServingEngine.submit",
        "ServingEngine._shed",
        "ServingEngine._expire",
        "ServingEngine._retry_after_hint",
        "ServingEngine._usable_slots",
        "ServingEngine._arrival_abs",
        "ServingEngine._pages_needed",
        "ServingEngine._release",
        "ServingEngine.page_accounting",
        "ServingEngine._prefix_lookup",
        "ServingEngine._reclaim_cached",
        "ServingEngine.take_results",
        "ServingEngine._oldest_age_s",
        "ServingEngine.health",
        "ServingEngine._write_gauges",
    ),
}

_DEVICE_CALLS = {"jax.device_get", "jax.block_until_ready",
                 "jax.device_put"}
_DEVICE_ATTR_CALLS = {"item", "block_until_ready"}


class HostSyncRule(Rule):
    id = "host-sync"
    description = ("jnp compute / device-array materialization in a "
                   "designated host-scheduling scope")

    def __init__(self,
                 host_modules: Sequence[str] = DEFAULT_HOST_MODULES,
                 host_functions: Mapping[str, Sequence[str]] = None):
        self.host_modules = frozenset(host_modules)
        hf = (DEFAULT_HOST_FUNCTIONS if host_functions is None
              else host_functions)
        self.host_functions = {k: tuple(v) for k, v in hf.items()}

    def _in_host_scope(self, mod: ModuleInfo, qname: str) -> bool:
        if mod.relpath in self.host_modules:
            return True
        prefixes = self.host_functions.get(mod.relpath, ())
        return any(qname == p or qname.startswith(p + ".")
                   for p in prefixes)

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        if (mod.relpath not in self.host_modules
                and mod.relpath not in self.host_functions):
            return []
        findings: List[Finding] = []
        for node, scopes in walk_scoped(mod.tree):
            qname = qualname(scopes)
            if not self._in_host_scope(mod, qname):
                continue
            scope_label = qname or "<module>"
            if isinstance(node, ast.Attribute):
                base = node.value
                if isinstance(base, ast.Name) and base.id == "jnp":
                    findings.append(Finding(
                        rule=self.id, path=mod.relpath, line=node.lineno,
                        message=(f"jnp.{node.attr} in host-scheduling "
                                 f"scope '{scope_label}' — device "
                                 "dispatch (and a hidden sync on "
                                 "fetch) in the tick-critical host "
                                 "path; route device work through the "
                                 "MeshExecutor entry points"),
                        key=f"jnp.{node.attr}@{scope_label}"))
            elif isinstance(node, ast.Call):
                callee = dotted_name(node.func)
                if callee in _DEVICE_CALLS:
                    findings.append(Finding(
                        rule=self.id, path=mod.relpath, line=node.lineno,
                        message=(f"{callee}() in host-scheduling scope "
                                 f"'{scope_label}' — blocks the "
                                 "scheduler on device completion"),
                        key=f"{callee}@{scope_label}"))
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in _DEVICE_ATTR_CALLS):
                    findings.append(Finding(
                        rule=self.id, path=mod.relpath, line=node.lineno,
                        message=(f".{node.func.attr}() in host-"
                                 f"scheduling scope '{scope_label}' — "
                                 "materializes a device value (hidden "
                                 "sync) if the receiver is a device "
                                 "array"),
                        key=f".{node.func.attr}@{scope_label}"))
        return findings
