"""JAX* rules: hot-path hygiene for serving/fold code.

"Hot zone" = modules whose path has a ``serving``/``ops``/``guard``
segment or is ``fold_in.py`` — the code that runs per query or per fold
tick, where one stray ``.item()`` stalls the dispatch pipeline and one
uncached ``jax.jit`` recompiles for seconds to minutes against
milliseconds of steady state.

Device-value taint is per-function and syntactic: a local assigned from
a ``jnp.*``/``jax.*`` call or a known-jitted callable is device-
resident; host conversions of tainted names (or any ``.item()`` in the
zone) are findings.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from predictionio_tpu.analysis.core import (Finding, FunctionInfo,
                                            RepoModel, attr_chain,
                                            jit_donated_positions,
                                            register_rule)

JAX001 = register_rule(
    "JAX001", "implicit host sync on hot path",
    ".item(), float()/int()/bool(), or np.asarray()/np.array() applied "
    "to a device value inside serving/fold code — each one blocks on "
    "the async dispatch queue and forces a device-to-host transfer per "
    "call. Batch the readback or keep the value on device.")

JAX002 = register_rule(
    "JAX002", "jit of closure (recompile hazard)",
    "jax.jit applied to a locally-defined function that captures "
    "enclosing variables. Every call of the enclosing function builds "
    "a NEW closure; jit's cache keys on function identity, so each "
    "build recompiles unless the wrapper is cached by the enclosing "
    "scope. Cache the jitted callable (module dict / lru_cache) keyed "
    "by the captured statics.")

JAX003 = register_rule(
    "JAX003", "jit constructed per call (uncached)",
    "jax.jit(...) executed inside a function body without a visible "
    "cache (no lru_cache decorator, result not stored in a cache "
    "container). On a per-request or per-tick path this recompiles "
    "every invocation — seconds to minutes of XLA time each.")

JAX004 = register_rule(
    "JAX004", "donated buffer reused after dispatch",
    "An argument at a donate_argnums position is used again after the "
    "jitted call. Donation invalidates the buffer; reuse returns "
    "garbage or raises depending on backend (and silently breaks when "
    "donation is re-enabled on TPU).")

JAX005 = register_rule(
    "JAX005", "serve-zone jit dispatch bypasses compile plane",
    "A module-level jitted callable is dispatched directly from "
    "serve-zone code (serving/guard modules, fold_in.py, the serve "
    "kernels ops/{als,similarity,topk}.py) by a function that never "
    "touches the compile plane (predictionio_tpu/compile: AOT registry "
    "dispatch, shared_jit, warm). Direct dispatch re-traces per shape "
    "and pays a full XLA compile whenever a vocabulary/batch/k size "
    "moves; plane dispatch gets shape-bucketed, deploy-warmed AOT "
    "executables (ISSUE 9).")

JAX006 = register_rule(
    "JAX006", "host sync in the pipelined serve zone",
    "A host-synchronizing call — jax.block_until_ready(), .item(), or "
    "np.asarray()/np.array() on a device value — inside the pipelined "
    "serving executor's modules (predictionio_tpu/serving/). ISSUE 14 "
    "keeps the serve path's formation/dispatch/serialization stages "
    "overlapped with device compute by deferring every readback to "
    "the completion stage's finish() closures (ops-layer *_begin "
    "kernels); one stray sync in serving/ code re-serializes the "
    "pipeline and silently gives back the overlap. The costmon "
    "1-in-N sampled sync lives in obs/costmon.py, outside this zone "
    "by construction; result readbacks belong in the ops-layer "
    "finish() callables, not in serving/ modules. The ONE sanctioned "
    "serve d2h site is ops/readback.py (ISSUE 19): begin_fetch() "
    "initiates copy_to_host_async at dispatch and its wait() closure "
    "attributes every second and byte — serving/ code that wants "
    "readback timing samples readback.thread_wait_s() deltas instead "
    "of touching a device handle.")

_HOT_SEGMENTS = {"serving", "ops", "guard"}


def in_hot_zone(relpath: str) -> bool:
    parts = relpath.split("/")
    return bool(_HOT_SEGMENTS.intersection(parts[:-1])) \
        or parts[-1] == "fold_in.py"


_DEVICE_ROOTS = {"jnp", "jax", "lax"}
_HOST_CASTS = {"float", "int", "bool"}
_NP_CONVERTERS = {("np", "asarray"), ("np", "array"),
                  ("numpy", "asarray"), ("numpy", "array"),
                  ("onp", "asarray"), ("onp", "array")}


def _tainted_names(fn: FunctionInfo) -> Set[str]:
    """Locals assigned from a jax/jnp call or a known-jitted callable
    anywhere in the function (flow-insensitive: assignment order inside
    branches isn't tracked, the zone restriction carries the signal)."""
    jitted = set(fn.module.jitted)
    for ev in fn.events:
        if ev.kind == "store" and ev.chain and ev.chain[-1] == "jit":
            jitted.add(ev.name)
    out: Set[str] = set()
    for ev in fn.events:
        if ev.kind != "store" or not ev.chain:
            continue
        root = ev.chain[0]
        if root in _DEVICE_ROOTS or root in jitted:
            out.add(ev.name)
    return out


def check_jax001(repo: RepoModel) -> List[Finding]:
    findings: List[Finding] = []
    for key, fn in repo.functions.items():
        if not in_hot_zone(fn.module.relpath):
            continue
        tainted = _tainted_names(fn)
        for ev in fn.events:
            if ev.kind != "call":
                continue
            chain, node = ev.chain, ev.node
            if chain[-1] == "item" and len(chain) >= 2:
                findings.append(Finding(
                    JAX001.id, fn.module.relpath, ev.line, fn.qualname,
                    f"item:{chain[-2]}",
                    f"{'.'.join(chain)}() forces a device sync per "
                    f"call"))
                continue
            arg0 = _first_arg_name(node)
            if arg0 is None or arg0 not in tainted:
                continue
            if len(chain) == 1 and chain[0] in _HOST_CASTS:
                findings.append(Finding(
                    JAX001.id, fn.module.relpath, ev.line, fn.qualname,
                    f"{chain[0]}:{arg0}",
                    f"{chain[0]}({arg0}) converts a device value on "
                    f"the host (implicit transfer + sync)"))
            elif tuple(chain[-2:]) in _NP_CONVERTERS:
                findings.append(Finding(
                    JAX001.id, fn.module.relpath, ev.line, fn.qualname,
                    f"asarray:{arg0}",
                    f"{'.'.join(chain)}({arg0}) pulls a device value "
                    f"to host memory (implicit transfer + sync)"))
    return findings


def _first_arg_name(node: Optional[ast.AST]) -> Optional[str]:
    if not isinstance(node, ast.Call) or not node.args:
        return None
    a = node.args[0]
    return a.id if isinstance(a, ast.Name) else None


def _free_vars(fn_node: ast.AST, params: Set[str]) -> Set[str]:
    """Names loaded but never bound in the function — closure captures
    (module globals are filtered by the caller)."""
    bound = set(params)
    loaded: Set[str] = set()
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Name):
            if isinstance(node.ctx, ast.Store):
                bound.add(node.id)
            elif isinstance(node.ctx, ast.Load):
                loaded.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node is not fn_node:
                bound.add(node.name)
    import builtins
    return {n for n in loaded - bound if not hasattr(builtins, n)}


def _jit_calls(fn: FunctionInfo):
    for ev in fn.events:
        if ev.kind in ("call", "store") and ev.chain \
                and ev.chain[-1] == "jit" and ev.node is not None:
            # the actual jit Call node: stores carry the Assign node
            node = ev.node
            if isinstance(node, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
                node = node.value
            if isinstance(node, ast.Call):
                yield ev, node


def check_jax002(repo: RepoModel) -> List[Finding]:
    findings: List[Finding] = []
    seen: Set[Tuple[str, int]] = set()
    for key, fn in repo.functions.items():
        nested_by_name = {repo.functions[k].name: repo.functions[k]
                          for k in fn.nested}
        module_globals = _module_globals(fn)
        for ev, call in _jit_calls(fn):
            if (fn.key, ev.line) in seen:
                continue
            seen.add((fn.key, ev.line))
            if not call.args or not isinstance(call.args[0], ast.Name):
                continue
            target = nested_by_name.get(call.args[0].id)
            if target is None:
                continue
            free = _free_vars(target.node, target.params)
            free -= module_globals
            free -= fn.module.imports.keys()
            if free:
                findings.append(Finding(
                    JAX002.id, fn.module.relpath, ev.line, fn.qualname,
                    f"closure:{target.name}",
                    f"jax.jit({target.name}) where {target.name} "
                    f"captures {sorted(free)} from the enclosing scope "
                    f"— a fresh closure per call recompiles unless the "
                    f"jitted wrapper is cached"))
    return findings


def _module_globals(fn: FunctionInfo) -> Set[str]:
    out: Set[str] = set()
    for node in fn.module.tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            out.add(node.name)
    return out


#: calls that hand a jitted callable to the compile plane for caching
#: (AOTRegistry.adopt / shared_jit): the registry owns its lifetime,
#: so the construction is a cached-jit pattern, not a recompile hazard
_PLANE_ADOPT_NAMES = {"adopt", "shared_jit"}


def _has_cache_exemption(fn: FunctionInfo, jit_store_name: str) -> bool:
    """The enclosing function visibly caches the jitted callable:
    lru_cache-decorated, the jit result stored into a subscript
    (``_CACHE[key] = fn``), or handed to the AOT registry
    (``AOT.adopt(key, jax.jit(impl))`` / stored then adopted) — the
    compile-plane idiom (ISSUE 9)."""
    for dec in getattr(fn.node, "decorator_list", []):
        chain = attr_chain(dec if not isinstance(dec, ast.Call)
                           else dec.func)
        if chain and chain[-1] in ("lru_cache", "cache"):
            return True
    for node in ast.walk(fn.node):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    v = node.value
                    if isinstance(v, ast.Name) and v.id == jit_store_name:
                        return True
                    if isinstance(v, ast.Call) and \
                            (attr_chain(v.func) or ())[-1:] == ("jit",):
                        return True
        elif isinstance(node, ast.Call):
            # terminal attribute name, resolvable even through a
            # call-rooted chain like get_aot().adopt(...)
            tail = (node.func.attr if isinstance(node.func, ast.Attribute)
                    else node.func.id if isinstance(node.func, ast.Name)
                    else None)
            if tail in _PLANE_ADOPT_NAMES:
                for a in node.args:
                    if isinstance(a, ast.Name) and jit_store_name \
                            and a.id == jit_store_name:
                        return True
                    if isinstance(a, ast.Call) and \
                            (attr_chain(a.func) or ())[-1:] == ("jit",):
                        return True
    return False


def check_jax003(repo: RepoModel) -> List[Finding]:
    findings: List[Finding] = []
    seen: Set[Tuple[str, int]] = set()
    for key, fn in repo.functions.items():
        for ev, call in _jit_calls(fn):
            if (fn.key, ev.line) in seen:
                continue
            seen.add((fn.key, ev.line))
            store_name = ev.name if ev.kind == "store" else ""
            if _has_cache_exemption(fn, store_name):
                continue
            findings.append(Finding(
                JAX003.id, fn.module.relpath, ev.line, fn.qualname,
                f"jit:{store_name or 'inline'}",
                f"jax.jit constructed inside {fn.qualname} with no "
                f"visible cache — recompiles on every invocation"))
    return findings


#: the serve zone: code dispatching device programs per query or per
#: fold tick — where the compile plane's shape buckets + AOT warming
#: are the contract. Narrower than the JAX001 hot zone: train-only
#: kernels (markov, forest, ...) re-trace once per run, not per tick.
_SERVE_KERNELS = {"als.py", "similarity.py", "topk.py"}


def in_serve_zone(relpath: str) -> bool:
    parts = relpath.split("/")
    # tenancy/ (ISSUE 15) joins the serve zone: the multi-tenant host
    # sits directly on the query path, so a jit dispatched there
    # without the compile plane recompiles per tenant shape.
    # dataplane/ (ISSUE 16) joins too: the bulk loader's steady phase
    # stages a chunk per iteration — a jit dispatched there without
    # the compile plane's pow2 buckets recompiles per chunk shape,
    # which is exactly the zero-steady-compile contract it must keep
    if {"serving", "guard", "tenancy", "dataplane"}.intersection(
            parts[:-1]):
        return True
    if parts[-1] == "fold_in.py":
        return True
    return "ops" in parts[:-1] and parts[-1] in _SERVE_KERNELS


_PLANE_MODULE_PREFIX = "predictionio_tpu.compile"
_PLANE_NAMES = {"get_aot", "shared_jit", "warm_models"}


def _references_plane(fn: FunctionInfo) -> bool:
    """Does this function resolve anything through the compile plane?
    Either by name (get_aot / shared_jit / warm_models, however
    imported) or through any alias the module imports from
    predictionio_tpu.compile.*."""
    imports = fn.module.imports
    for ev in fn.events:
        if not ev.chain:
            continue
        root = ev.chain[0]
        if root in _PLANE_NAMES or "shared_jit" in ev.chain \
                or "get_aot" in ev.chain:
            return True
        if imports.get(root, "").startswith(_PLANE_MODULE_PREFIX):
            return True
    return False


def check_jax005(repo: RepoModel) -> List[Finding]:
    findings: List[Finding] = []
    seen: Set[Tuple[str, str]] = set()
    for key, fn in repo.functions.items():
        if not in_serve_zone(fn.module.relpath):
            continue
        roster = set(fn.module.jitted)
        if not roster or _references_plane(fn):
            continue
        for ev in fn.events:
            if ev.kind != "call" or len(ev.chain) != 1 \
                    or ev.chain[0] not in roster:
                continue
            if (fn.key, ev.chain[0]) in seen:
                continue
            seen.add((fn.key, ev.chain[0]))
            findings.append(Finding(
                JAX005.id, fn.module.relpath, ev.line, fn.qualname,
                f"jit_dispatch:{ev.chain[0]}",
                f"{fn.qualname} dispatches jitted {ev.chain[0]} "
                f"directly on a serve-zone path — no compile-plane "
                f"resolution (shape buckets / AOT warm) covers it"))
    return findings


#: the pipelined serve zone (ISSUE 14): the executor's own modules,
#: where NO host sync may appear — readbacks live in the ops-layer
#: finish() closures and the sampled sync in obs/costmon.py, both
#: outside this zone. Narrower than the JAX001 hot zone on purpose:
#: the ops kernels legitimately np.asarray inside their finish()
#: callables (that IS the completion stage).
def in_pipelined_zone(relpath: str) -> bool:
    parts = relpath.split("/")
    # tenancy/ routes into the pipelined executor (ISSUE 15): a host
    # sync there would stall every tenant's overlap, not just one's.
    # dataplane/ (ISSUE 16) is pipelined the same way: read/decode of
    # chunk N+1 overlaps the async upload of chunk N, and the only
    # legitimate syncs live in ops/staging.py (device_stage submit,
    # wait_ready) — a sync in dataplane/ re-serializes the backfill
    return bool({"serving", "tenancy", "dataplane"}.intersection(
        parts[:-1]))


def check_jax006(repo: RepoModel) -> List[Finding]:
    findings: List[Finding] = []
    for key, fn in repo.functions.items():
        if not in_pipelined_zone(fn.module.relpath):
            continue
        tainted = _tainted_names(fn)
        for ev in fn.events:
            if ev.kind != "call" or not ev.chain:
                continue
            chain, node = ev.chain, ev.node
            if chain[-1] == "block_until_ready":
                findings.append(Finding(
                    JAX006.id, fn.module.relpath, ev.line, fn.qualname,
                    "block_until_ready",
                    f"{'.'.join(chain)}() synchronizes on the device "
                    f"inside the pipelined serve zone — the overlap "
                    f"ISSUE 14 bought is re-serialized here"))
                continue
            if chain[-1] == "item" and len(chain) >= 2:
                findings.append(Finding(
                    JAX006.id, fn.module.relpath, ev.line, fn.qualname,
                    f"item:{chain[-2]}",
                    f"{'.'.join(chain)}() forces a device sync in the "
                    f"pipelined serve zone"))
                continue
            arg0 = _first_arg_name(node)
            if arg0 is not None and arg0 in tainted \
                    and tuple(chain[-2:]) in _NP_CONVERTERS:
                findings.append(Finding(
                    JAX006.id, fn.module.relpath, ev.line, fn.qualname,
                    f"asarray:{arg0}",
                    f"{'.'.join(chain)}({arg0}) reads a device value "
                    f"back in the pipelined serve zone — defer it to "
                    f"the completion stage's finish()"))
    return findings


def check_jax004(repo: RepoModel) -> List[Finding]:
    findings: List[Finding] = []
    for key, fn in repo.functions.items():
        donating = dict(fn.module.jitted)   # name -> positions
        donating = {n: p for n, p in donating.items() if p}
        for ev in fn.events:                # local jit wrappers
            if ev.kind == "store" and ev.chain \
                    and ev.chain[-1] == "jit" and ev.node is not None:
                node = ev.node
                if isinstance(node, ast.Assign) \
                        and isinstance(node.value, ast.Call):
                    pos = jit_donated_positions(node.value)
                    if pos:
                        donating[ev.name] = pos
        if not donating:
            continue
        # calls to donating wrappers: donated positional Name args must
        # not be loaded after the call line
        for ev in fn.events:
            if ev.kind != "call" or len(ev.chain) != 1 \
                    or ev.chain[0] not in donating:
                continue
            call = ev.node
            if not isinstance(call, ast.Call):
                continue
            for pos in donating[ev.chain[0]]:
                if pos >= len(call.args):
                    continue
                arg = call.args[pos]
                if not isinstance(arg, ast.Name):
                    continue
                # rebinding kills the hazard: `G = f(G)` in a loop
                # re-points the name at the RESULT buffer, so loads
                # after the re-store (including next iteration's arg)
                # are safe. Only loads between donation and the next
                # store of the name are findings.
                restore = min((s.line for s in fn.events
                               if s.kind in ("store", "tuplestore")
                               and s.name == arg.id
                               and s.line >= ev.line),
                              default=None)
                # the call's own argument lines are the donation itself,
                # not a reuse (a multi-line call lists its args below
                # the line the call starts on)
                call_end = getattr(call, "end_lineno", None) or ev.line
                for later in fn.events:
                    if later.kind != "load" or later.name != arg.id \
                            or later.line <= call_end:
                        continue
                    if restore is not None and later.line > restore:
                        continue
                    findings.append(Finding(
                        JAX004.id, fn.module.relpath, later.line,
                        fn.qualname, f"donated:{arg.id}",
                        f"{arg.id} donated to {ev.chain[0]} at "
                        f"line {ev.line} is used again — the "
                        f"buffer is invalid after donation"))
                    break
    return findings
