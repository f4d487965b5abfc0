"""Env-registry pass (rules ``env-unregistered``, ``env-undocumented``,
``env-dead``, ``env-dynamic``).

The bug class (ISSUE 11): env knobs rot silently. PR 6 shipped
``TORCHSNAPSHOT_TPU_STORE_LEASE_S`` and a refactor later made it dead in
external-store mode with no test noticing; ``STORE_RPC_TIMEOUT`` was
read by ``dist_store`` but never documented, so nobody tuning a
deployment could find it. The fix is a closed-world registry: every
``TORCHSNAPSHOT_TPU_*`` name the package reads MUST appear in
:data:`ENV_REGISTRY` below, every registry entry MUST have a row in
``docs/source/utilities.rst``, and (when scanning the real package)
every registry entry MUST still be read somewhere — three failure modes
(``env-unregistered``, ``env-undocumented``, ``env-dead``), each caught
the moment a PR introduces it.

Reads are found at ``os.environ.get/[]``, ``os.getenv``, ``pop`` and
``setdefault``; the name argument is resolved through literals,
module-level constants, and constants imported from sibling modules. A
name that flows through a module-level helper's parameter (the
``integrity._enabled(name)`` idiom) is resolved at each call site via
the call graph. A read whose name cannot be resolved statically at all
is ``env-dynamic`` — an unresolvable read is an unauditable knob.

Foreign variables (``JAX_PLATFORMS`` etc.) are out of scope: the
registry governs only the package's own prefix.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from ..core import Finding, FunctionInfo, Module, PACKAGE_DIR, REPO_DIR, Project, dotted

RULES = ("env-unregistered", "env-undocumented", "env-dead", "env-dynamic")

ENV_PREFIX = "TORCHSNAPSHOT_TPU_"

#: The closed-world knob registry. Adding an env read to the package
#: means adding its literal here AND a row to docs/source/utilities.rst
#: (the pass enforces both); removing the last read of a knob means
#: deleting it here, or ``env-dead`` fires.
ENV_REGISTRY = frozenset({
    "TORCHSNAPSHOT_TPU_BARRIER_TIMEOUT",
    "TORCHSNAPSHOT_TPU_CHECKSUM",
    "TORCHSNAPSHOT_TPU_CLOUD_IO_THREADS",
    "TORCHSNAPSHOT_TPU_COMPRESSION",
    "TORCHSNAPSHOT_TPU_COOP_RESTORE",
    "TORCHSNAPSHOT_TPU_COOP_TIMEOUT",
    "TORCHSNAPSHOT_TPU_CPU_CONCURRENCY",
    "TORCHSNAPSHOT_TPU_DEVICE_DIGESTS",
    "TORCHSNAPSHOT_TPU_DISABLE_NATIVE",
    "TORCHSNAPSHOT_TPU_ENABLE_BATCHING",
    "TORCHSNAPSHOT_TPU_FAULT_PLAN",
    "TORCHSNAPSHOT_TPU_FLIGHTREC",
    "TORCHSNAPSHOT_TPU_FLIGHTREC_DIR",
    "TORCHSNAPSHOT_TPU_FLIGHTREC_RING",
    "TORCHSNAPSHOT_TPU_FLIGHTREC_SIGTERM",
    "TORCHSNAPSHOT_TPU_FORENSICS",
    "TORCHSNAPSHOT_TPU_FORENSICS_DEADLINE_FRAC",
    "TORCHSNAPSHOT_TPU_FORENSICS_SAMPLE_S",
    "TORCHSNAPSHOT_TPU_FORENSICS_STALL_S",
    "TORCHSNAPSHOT_TPU_FSYNC",
    "TORCHSNAPSHOT_TPU_HEARTBEAT_S",
    "TORCHSNAPSHOT_TPU_IO_CONCURRENCY",
    "TORCHSNAPSHOT_TPU_HOT_SET",
    "TORCHSNAPSHOT_TPU_JOURNAL",
    "TORCHSNAPSHOT_TPU_JOURNAL_EPOCH_BYTES",
    "TORCHSNAPSHOT_TPU_JOURNAL_MAX_EPOCHS",
    "TORCHSNAPSHOT_TPU_LAZY_RESTORE",
    "TORCHSNAPSHOT_TPU_LINT_BASELINE",
    "TORCHSNAPSHOT_TPU_METRICS_PORT",
    "TORCHSNAPSHOT_TPU_MMAP_READS",
    "TORCHSNAPSHOT_TPU_NATIVE_ALIGN",
    "TORCHSNAPSHOT_TPU_NATIVE_IO",
    "TORCHSNAPSHOT_TPU_NATIVE_ODIRECT",
    "TORCHSNAPSHOT_TPU_NATIVE_QUEUE_DEPTH",
    "TORCHSNAPSHOT_TPU_PAGEIN_PREFETCH",
    "TORCHSNAPSHOT_TPU_PER_RANK_MEMORY_BUDGET_BYTES",
    "TORCHSNAPSHOT_TPU_PREVERIFY",
    "TORCHSNAPSHOT_TPU_PROGRESS_S",
    "TORCHSNAPSHOT_TPU_RESHARD",
    "TORCHSNAPSHOT_TPU_RESHARD_MIN_REQUESTERS",
    "TORCHSNAPSHOT_TPU_SEED_FANOUT",
    "TORCHSNAPSHOT_TPU_SEED_RESTORE",
    "TORCHSNAPSHOT_TPU_SEED_TTL_S",
    "TORCHSNAPSHOT_TPU_ADMISSION",
    "TORCHSNAPSHOT_TPU_MANIFEST_FORMAT",
    "TORCHSNAPSHOT_TPU_QUOTA_BYTES",
    "TORCHSNAPSHOT_TPU_STAGING_POOL_BYTES",
    "TORCHSNAPSHOT_TPU_STORE_ADDR",
    "TORCHSNAPSHOT_TPU_TENANT",
    "TORCHSNAPSHOT_TPU_STORE_CONNECT_RETRIES",
    "TORCHSNAPSHOT_TPU_STORE_LEASE_S",
    "TORCHSNAPSHOT_TPU_STORE_REPLICAS",
    "TORCHSNAPSHOT_TPU_STORE_RPC_TIMEOUT",
    "TORCHSNAPSHOT_TPU_STREAM_READS",
    "TORCHSNAPSHOT_TPU_STREAM_WRITES",
    "TORCHSNAPSHOT_TPU_SUB_CHUNK_BYTES",
    "TORCHSNAPSHOT_TPU_SUB_CHUNK_MAX_BYTES",
    "TORCHSNAPSHOT_TPU_SUB_CHUNK_MIN_BYTES",
    "TORCHSNAPSHOT_TPU_TELEMETRY",
    "TORCHSNAPSHOT_TPU_TELEMETRY_MAX_EVENTS",
    "TORCHSNAPSHOT_TPU_TREND_THRESHOLD",
    "TORCHSNAPSHOT_TPU_UPDATE_PUSH",
    "TORCHSNAPSHOT_TPU_VERIFY",
    "TORCHSNAPSHOT_TPU_GEOREP",
    "TORCHSNAPSHOT_TPU_GEOREP_INTERVAL_S",
    "TORCHSNAPSHOT_TPU_GEOREP_BACKLOG",
    "TORCHSNAPSHOT_TPU_GEOREP_DRAIN_S",
})

UTILITIES_RST = os.path.join(REPO_DIR, "docs", "source", "utilities.rst")

_READ_CALLS = {
    "os.environ.get", "environ.get",
    "os.environ.pop", "environ.pop",
    "os.environ.setdefault", "environ.setdefault",
    "os.getenv", "getenv",
}


def _documented_names() -> Set[str]:
    try:
        with open(UTILITIES_RST, "r") as f:
            text = f.read()
    except OSError:
        return set()
    return set(re.findall(r"TORCHSNAPSHOT_TPU_[A-Z0-9_]*[A-Z0-9]", text))


def _env_read_arg(node: ast.AST) -> Optional[Tuple[ast.AST, int]]:
    """(name-expression, line) if this node reads an env var."""
    if isinstance(node, ast.Call):
        name = dotted(node.func)
        if name in _READ_CALLS and node.args:
            return node.args[0], node.lineno
        return None
    if isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
        base = dotted(node.value)
        if base in ("os.environ", "environ"):
            return node.slice, node.lineno
    return None


def _param_index(info: FunctionInfo, name: str) -> Optional[int]:
    node = info.node
    if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return None
    for i, arg in enumerate(node.args.args):
        if arg.arg == name:
            return i
    return None


def run_pass(project: Project) -> List[Finding]:
    reads: List[Tuple[str, str, int]] = []  # (env name, file, line)
    dynamic: List[Tuple[str, int, str]] = []  # (file, line, detail)
    #: module-level functions whose parameter carries the env name:
    #: qualname -> (info, param index, read site)
    param_flows: Dict[str, Tuple[FunctionInfo, int, Tuple[str, int]]] = {}

    def scan(mod: Module, root: ast.AST, info: Optional[FunctionInfo]) -> None:
        for node in ast.iter_child_nodes(root):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # functions are scanned with their own context
            hit = _env_read_arg(node)
            if hit is not None:
                arg, line = hit
                val = project.resolve_const(mod, arg)
                if val is not None:
                    reads.append((val, mod.rel, line))
                elif (
                    info is not None
                    and info.class_name is None
                    and isinstance(arg, ast.Name)
                    and _param_index(info, arg.id) is not None
                ):
                    idx = _param_index(info, arg.id)
                    assert idx is not None
                    param_flows.setdefault(
                        info.qualname, (info, idx, (mod.rel, line))
                    )
                else:
                    dynamic.append(
                        (mod.rel, line,
                         "env var name is not a literal, registered "
                         "constant, or resolvable parameter")
                    )
            scan(mod, node, info)

    for mod in project.modules:
        scan(mod, mod.tree, None)
    for mod, info in project.walk_functions():
        scan(mod, info.node, info)

    # second pass: resolve parameter-carried names at their call sites.
    # The walk covers each module's ENTIRE tree (module-level constant
    # initialization like ``DEFAULT = _read_env_number(VAR, 5.0)`` is the
    # dominant idiom, and it is not inside any function).
    for qualname, (target, idx, read_site) in sorted(param_flows.items()):
        resolved_any = False
        for mod in project.modules:
            info = FunctionInfo(
                module_rel=mod.rel, class_name=None, name="<module>",
                node=mod.tree,
            )
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                if not any(
                    c.qualname == qualname
                    for c in project.resolve_call(mod, info, node)
                ):
                    continue
                if len(node.args) > idx:
                    val = project.resolve_const(mod, node.args[idx])
                    if val is not None:
                        reads.append((val, mod.rel, node.lineno))
                        resolved_any = True
                        continue
                dynamic.append(
                    (mod.rel, node.lineno,
                     f"call into {qualname} does not pass a resolvable "
                     "env var name")
                )
        if not resolved_any:
            dynamic.append(
                (read_site[0], read_site[1],
                 f"no call site passes a resolvable env name into "
                 f"{qualname}")
            )

    findings: Dict[Tuple[str, str, int], Finding] = {}
    docs = _documented_names()
    is_real_package = os.path.realpath(project.package_dir) == os.path.realpath(
        PACKAGE_DIR
    )
    seen_names: Set[str] = set()
    for name, rel, line in reads:
        if not name.startswith(ENV_PREFIX):
            continue
        seen_names.add(name)
        if name not in ENV_REGISTRY:
            findings.setdefault(
                ("env-unregistered", rel, line),
                Finding(
                    rule="env-unregistered", file=rel, line=line,
                    message=(
                        f"reads {name}, which is not in ENV_REGISTRY "
                        "(analysis/plugins/envreg.py) — register it and "
                        "document it in docs/source/utilities.rst"
                    ),
                ),
            )
        elif is_real_package and docs and name not in docs:
            findings.setdefault(
                ("env-undocumented", rel, line),
                Finding(
                    rule="env-undocumented", file=rel, line=line,
                    message=(
                        f"{name} is registered but has no row in "
                        "docs/source/utilities.rst — undocumented knobs "
                        "don't exist for operators"
                    ),
                ),
            )
    for rel, line, detail in dynamic:
        findings.setdefault(
            ("env-dynamic", rel, line),
            Finding(
                rule="env-dynamic", file=rel, line=line,
                message=f"unauditable environ read: {detail}",
            ),
        )
    if is_real_package:
        self_mod = project.module(
            os.path.join("analysis", "plugins", "envreg.py").replace(os.sep, "/")
        )
        for name in sorted(ENV_REGISTRY - seen_names):
            line = 1
            if self_mod is not None:
                for i, text in enumerate(self_mod.lines, start=1):
                    if f'"{name}"' in text:
                        line = i
                        break
            findings.setdefault(
                ("env-dead", name, line),
                Finding(
                    rule="env-dead",
                    file=(
                        self_mod.rel if self_mod is not None
                        else "torchsnapshot_tpu/analysis/plugins/envreg.py"
                    ),
                    line=line,
                    message=(
                        f"{name} is registered but nothing in the package "
                        "reads it — delete the knob (and its utilities.rst "
                        "row) or wire it back up"
                    ),
                ),
            )
    out = list(findings.values())
    out.sort(key=lambda f: (f.file, f.line, f.rule, f.message))
    return out
