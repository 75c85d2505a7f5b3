"""The repo-specific lint rules.

Each rule encodes an architectural invariant of the serving stack (see
docs/INVARIANTS.md for the catalogue).  Rules are deliberately
*codebase-aware*: the scope registries below name the exact hot paths,
sanctioned writers, and registered bucketing helpers, so a new call site
has to either follow the discipline or earn an allowlist entry with a
written justification.
"""
from __future__ import annotations

import ast
import fnmatch
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.obs.spans import SPAN_NAMES

from .lint import Finding, ModuleInfo, Rule, dotted_name, first_arg_src


# ==========================================================================
# registries (the codebase-aware part)
# ==========================================================================

# --- sync-point -----------------------------------------------------------
# Per-token / per-chunk hot scopes: any host<->device synchronization here
# must be a declared dispatch point (allowlisted) or it stalls the decode
# tail that DuoServe's prefetch overlap is supposed to protect.
SYNC_HOT_SCOPES: Dict[str, Tuple[str, ...]] = {
    "serving/engine.py": (
        "EngineCore._grouped_ffn_raw",
        "EngineCore._run_experts_prefill",
        "EngineCore._run_experts_prefill_fused",
        "EngineCore._prefill_moe",
        "EngineCore._sample",
        "MoEServingEngine.prefill",
        "EngineCore.prefill_chunk",
        "EngineCore.prefill_chunks",
        "MoEServingEngine._prefill_layers_chunked",
        "MoEServingEngine.decode",
    ),
    "serving/batching.py": (
        "BatchedServingEngine.step",
        "BatchedServingEngine._decode_step",
        "BatchedServingEngine._prefill_work",
        "BatchedServingEngine._run_prefill_chunks",
        "BatchedServingEngine._admit_and_prefill",
        "BatchedServingEngine._sample_req",
        "BatchedServingEngine._emit_token",
    ),
    "core/cache.py": (
        "CacheState.*",
        "ExpertResidency.*",
        "_pool_write",
    ),
    "kernels/*.py": ("*",),
}

# Callables that force a host sync (or a host->device transfer) when handed
# a device value.  jnp.asarray is deliberately absent: it dispatches on
# device without a readback.
SYNC_CALLS: Set[str] = {
    "np.asarray", "np.array", "np.fromiter",
    "numpy.asarray", "numpy.array", "numpy.fromiter",
    "asarray", "fromiter",
    "jax.device_put", "jax.device_get", "device_put", "device_get",
    "float",
}
SYNC_METHODS: Set[str] = {"item", "block_until_ready", "tolist", "to_py"}

# --- emit-discipline ------------------------------------------------------
# The one sink every streamed token funnels through (PR 4); the event
# buffer itself is only touched by EngineCore._emit.
EMIT_BUFFER_OWNER = "EngineCore._emit"
TOKEN_EVENT_SINKS: Tuple[str, ...] = (
    "BatchedServingEngine._emit_token",
    "MoEServingEngine._emit_token",
)

# --- residency-discipline -------------------------------------------------
# Device-resident state with exactly one owner: the expert slot pools
# belong to ExpertResidency (PR 3); the KV slot pools and the slot_pos
# ledger belong to the declared engine writers below.
PROTECTED_STATE: Set[str] = {"_pools", "_K", "_V", "_slot_pos"}
RESIDENCY_WRITERS: Tuple[str, ...] = (
    "ExpertResidency.*",                     # the pools' owner (core/cache.py)
    "BatchedServingEngine.__init__",         # allocation
    "BatchedServingEngine._decode_step",     # per-step KV append
    "BatchedServingEngine._run_prefill_chunks",
    "BatchedServingEngine._admit_and_prefill",
    "BatchedServingEngine.restore",          # snapshot handoff scatter
    "BatchedServingEngine._release_slot",    # slot_pos invalidation
)

# --- jit-hygiene ----------------------------------------------------------
# In the serving stack every jitted kernel is defined once, at engine
# construction, inside EngineCore._jit_fns; core/ and kernels/ may define
# module-level jitted functions.  jax.jit in a loop body or invoked inline
# re-traces per call.
JIT_SETUP_SCOPES: Tuple[str, ...] = ("EngineCore._jit_fns", "EngineCore._jit_fns.*")
SERVING_JIT_FILES: Tuple[str, ...] = (
    "serving/engine.py", "serving/batching.py", "serving/cluster.py",
    "serving/frontend.py",
)
# self.<attr> that jitted closures must NOT capture: mutable per-request /
# per-step state.  Capturing one freezes a stale value into the trace (or
# worse, retraces per object identity).
JIT_MUTABLE_SELF: Set[str] = {
    "cache", "sched", "store", "perf", "prefix", "queue", "dev",
    "running", "prefilling", "_K", "_V", "_slot_pos", "_events", "_pools",
    "_free_slots", "_arrivals", "metrics", "obs",
}

# --- recompile-hazard -----------------------------------------------------
# Jitted callees reachable from the engines; an argument whose shape is
# data-dependent (slice bounds / constructed shapes from un-bucketed
# values) recompiles per value.
REGISTERED_JIT_CALLEES: Set[str] = {
    "_attn_prefill", "_attn_prefill_chunk", "_attn_decode",
    "_attn_decode_batched", "_gate", "_expert_raw", "_grouped_raw",
    "_expert", "_shared", "_head", "_concat_rows", "_split_rows",
    "expert_ffn", "expert_ffn_from_pool", "_pool_write",
}
# Helpers whose results are *sanctioned* shape sources: power-of-two
# bucketing keeps the distinct-shape count logarithmic.
BUCKETING_HELPERS: Set[str] = {"_bucket", "group_by_expert", "vocab_pad_of"}

# --- obs-discipline -------------------------------------------------------
# (a) Aggregates migrated onto the repro.obs metrics registry (PR 10).
# The old attribute names survive as read-only registry views; a direct
# write bypasses the registry and silently forks the bookkeeping.
MIGRATED_METRICS: Set[str] = {
    # BatchedServingEngine
    "prefilled_tokens",
    # ReplicaPool
    "n_handoffs", "n_migrated", "handoff_bytes", "handoff_bytes_saved",
    "n_tail_handoffs",
    # QosAutopilot
    "n_shed", "by_reason", "n_preempted", "n_resumed",
}
# (b) Span lifecycle discipline: SpanRecorder mutators may be called only
# at the declared request-lifecycle / engine-phase points below, so the
# span taxonomy stays small enough to read as a timeline, and every
# interval opened (``begin``) is named by a literal in
# ``repro.obs.spans.SPAN_NAMES``, the list trace readers select by.
# Read-only recorder views (spans(), terminal_reasons(), ...) are fine
# anywhere.
SPAN_METHODS: Set[str] = {"begin", "end", "instant", "terminal"}
SPAN_SCOPES: Dict[str, Tuple[str, ...]] = {
    "core/cache.py": (
        "ExpertResidency.prefetch",
        "ExpertResidency.wait",
    ),
    "serving/engine.py": (
        "EngineCore.prefill_chunks",         # one sweep, several requests
        "EngineCore.prefill_layers",
        "EngineCore._prefill_moe",
        "MoEServingEngine.decode",
    ),
    "serving/batching.py": (
        "BatchedServingEngine.submit_request",
        "BatchedServingEngine._admit_and_prefill",
        "BatchedServingEngine._run_prefill_chunks",   # prefill.chunk
        "BatchedServingEngine._decode_step",
        "BatchedServingEngine.cancel",
        "BatchedServingEngine._retire",
        "BatchedServingEngine.snapshot",
        "BatchedServingEngine.restore",
    ),
    "serving/cluster.py": (
        "ReplicaPool.migrate",
        "QosAutopilot.scan",
        "QosAutopilot._scan_preempt",
    ),
    "serving/frontend.py": (
        "CooperativeDriver._cancel_paused",
    ),
}


# ==========================================================================
# helpers
# ==========================================================================


def _scope_matches(scope: str, patterns: Sequence[str]) -> bool:
    return any(fnmatch.fnmatch(scope, p) for p in patterns)


def _is_host_literal(node: ast.AST) -> bool:
    """Args that are plainly host-side: literals and comprehensions over
    host lists.  np.asarray over these is list->array packing, not a
    device sync."""
    return isinstance(
        node,
        (ast.List, ast.Tuple, ast.Set, ast.Dict, ast.ListComp,
         ast.SetComp, ast.DictComp, ast.GeneratorExp, ast.Constant),
    )


def _attr_root(node: ast.AST) -> Optional[str]:
    """For a target like ``self._K[l]`` or ``self._pools["w1"]`` return the
    protected attribute name (``_K``); None if not an attribute chain."""
    while isinstance(node, (ast.Subscript, ast.Starred)):
        node = node.value
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _iter_target_roots(target: ast.AST) -> Iterable[ast.AST]:
    if isinstance(target, (ast.Tuple, ast.List)):
        for elt in target.elts:
            yield from _iter_target_roots(elt)
    else:
        yield target


def _is_jax_jit(node: ast.AST) -> bool:
    """True for ``jax.jit`` / ``jit`` names and for
    ``functools.partial(jax.jit, ...)`` calls."""
    name = dotted_name(node)
    if name in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call):
        fn = dotted_name(node.func)
        if fn in ("functools.partial", "partial"):
            return any(_is_jax_jit(a) for a in node.args)
    return False


# ==========================================================================
# rules
# ==========================================================================


class SyncPointRule(Rule):
    id = "sync-point"
    paths = tuple(SYNC_HOT_SCOPES)

    def check(self, mod: ModuleInfo) -> Iterable[Finding]:
        patterns: Tuple[str, ...] = ()
        for glob, pats in SYNC_HOT_SCOPES.items():
            if fnmatch.fnmatch(mod.relpath, glob):
                patterns = patterns + pats
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            scope = mod.scope(node)
            if not _scope_matches(scope, patterns):
                continue
            name = dotted_name(node.func)
            finding = None
            if name in SYNC_CALLS:
                if node.args and _is_host_literal(node.args[0]):
                    continue
                finding = name
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in SYNC_METHODS
            ):
                finding = dotted_name(node.func)
            if finding is None:
                continue
            yield Finding(
                rule=self.id,
                path=mod.relpath,
                line=node.lineno,
                scope=scope,
                message=(
                    f"host sync `{finding}` on a per-token/per-chunk path; "
                    "syncs belong at declared dispatch points "
                    "(allowlist with justification if this is one)"
                ),
                call=finding,
                arg=first_arg_src(node),
            )


class EmitDisciplineRule(Rule):
    id = "emit-discipline"
    paths = ("serving/*.py",)

    def check(self, mod: ModuleInfo) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            scope = mod.scope(node)
            name = dotted_name(node.func)
            # (a) direct event-buffer append
            if name.endswith("._events.append") and scope != EMIT_BUFFER_OWNER:
                yield Finding(
                    rule=self.id, path=mod.relpath, line=node.lineno,
                    scope=scope, call=name, arg=first_arg_src(node),
                    message=(
                        "event buffer appended outside EngineCore._emit; "
                        "route events through self._emit(...)"
                    ),
                )
            # (b) TokenEvent construction outside the one token sink
            if name.split(".")[-1] == "TokenEvent" and not _scope_matches(
                scope, TOKEN_EVENT_SINKS
            ):
                yield Finding(
                    rule=self.id, path=mod.relpath, line=node.lineno,
                    scope=scope, call="TokenEvent", arg=first_arg_src(node),
                    message=(
                        "TokenEvent constructed outside the _emit_token sink; "
                        "every streamed token must funnel through one sink "
                        "so cancellation/TBT accounting stay exact"
                    ),
                )


class ResidencyDisciplineRule(Rule):
    id = "residency-discipline"
    paths = ("serving/*.py", "core/*.py")

    def check(self, mod: ModuleInfo) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            else:
                continue
            scope = mod.scope(node)
            for t in targets:
                for root in _iter_target_roots(t):
                    attr = _attr_root(root)
                    if attr not in PROTECTED_STATE:
                        continue
                    # ExpertResidency owns _pools; engine writers own KV
                    if _scope_matches(scope, RESIDENCY_WRITERS):
                        continue
                    yield Finding(
                        rule=self.id, path=mod.relpath, line=node.lineno,
                        scope=scope, call=attr,
                        arg=ast.unparse(t) if hasattr(ast, "unparse") else "",
                        message=(
                            f"mutation of protected device state `{attr}` "
                            "outside its declared owner scopes "
                            "(ExpertResidency / registered engine KV writers)"
                        ),
                    )


class JitHygieneRule(Rule):
    id = "jit-hygiene"
    paths = ("serving/*.py", "core/*.py", "kernels/*.py")

    def check(self, mod: ModuleInfo) -> Iterable[Finding]:
        yield from self._check_jit_calls(mod)
        if mod.relpath == "serving/engine.py":
            yield from self._check_closures(mod)

    def _check_jit_calls(self, mod: ModuleInfo) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call) and _is_jax_jit(node.func)):
                continue
            scope = mod.scope(node)
            if mod.loops(node) > 0:
                yield Finding(
                    rule=self.id, path=mod.relpath, line=node.lineno,
                    scope=scope, call="jax.jit",
                    message=(
                        "jax.jit invoked inside a loop body: a fresh jitted "
                        "callable per iteration defeats the compile cache"
                    ),
                )
                continue
            if (
                mod.relpath in SERVING_JIT_FILES
                and scope
                and not _scope_matches(scope, JIT_SETUP_SCOPES)
            ):
                yield Finding(
                    rule=self.id, path=mod.relpath, line=node.lineno,
                    scope=scope, call="jax.jit",
                    message=(
                        "jax.jit in a serving method body; jitted kernels are "
                        "defined once in EngineCore._jit_fns at construction"
                    ),
                )
        # immediately-invoked form: Call(func=Call(func=jax.jit))
        for node in ast.walk(mod.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Call)
                and _is_jax_jit(node.func.func)
            ):
                yield Finding(
                    rule=self.id, path=mod.relpath, line=node.lineno,
                    scope=mod.scope(node), call="jax.jit",
                    message=(
                        "jax.jit(f)(...) invoked inline: the wrapper is "
                        "rebuilt (and retraced) on every call"
                    ),
                )

    def _check_closures(self, mod: ModuleInfo) -> Iterable[Finding]:
        """Inside EngineCore._jit_fns, jitted inner defs must not close over
        mutable per-request engine state."""
        fn = mod.functions.get("EngineCore._jit_fns")
        if fn is None:
            return
        for node in ast.walk(fn):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            jitted = any(_is_jax_jit(d) for d in node.decorator_list)
            if not jitted:
                continue
            for sub in ast.walk(node):
                if (
                    isinstance(sub, ast.Attribute)
                    and isinstance(sub.value, ast.Name)
                    and sub.value.id == "self"
                    and sub.attr in JIT_MUTABLE_SELF
                ):
                    yield Finding(
                        rule=self.id, path=mod.relpath, line=sub.lineno,
                        scope=f"EngineCore._jit_fns.{node.name}",
                        call=f"self.{sub.attr}",
                        message=(
                            f"jitted kernel closes over mutable engine state "
                            f"`self.{sub.attr}`: the traced value goes stale "
                            "(pass it as an argument instead)"
                        ),
                    )


class RecompileHazardRule(Rule):
    id = "recompile-hazard"
    paths = ("serving/*.py", "kernels/*.py")

    def check(self, mod: ModuleInfo) -> Iterable[Finding]:
        for qual, fn in mod.functions.items():
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            blessed = self._blessed_names(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if mod.scope(node) != qual:
                    continue
                callee = dotted_name(node.func).split(".")[-1]
                if callee not in REGISTERED_JIT_CALLEES:
                    continue
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    for issue, line in self._shape_hazards(arg, blessed):
                        yield Finding(
                            rule=self.id, path=mod.relpath, line=line,
                            scope=qual, call=callee,
                            arg=ast.unparse(arg),
                            message=(
                                f"data-dependent shape crosses the jit "
                                f"boundary of `{callee}`: {issue}; route it "
                                "through a registered bucketing helper "
                                "(_bucket / group_by_expert / vocab_pad_of)"
                            ),
                        )

    # -- taint: names derived from bucketing helpers are sanctioned --------

    def _blessed_names(self, fn: ast.AST) -> Set[str]:
        blessed: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assign):
                    continue
                if self._value_blessed(node.value, blessed):
                    for t in node.targets:
                        for root in _iter_target_roots(t):
                            if isinstance(root, ast.Name) and root.id not in blessed:
                                blessed.add(root.id)
                                changed = True
        return blessed

    def _value_blessed(self, value: ast.AST, blessed: Set[str]) -> bool:
        if isinstance(value, ast.Call):
            callee = dotted_name(value.func).split(".")[-1]
            if callee in BUCKETING_HELPERS:
                return True
        # attribute / subscript of a blessed name (disp.row_idx, shp[0])
        node = value
        while isinstance(node, (ast.Attribute, ast.Subscript)):
            node = node.value
        if isinstance(node, ast.Name) and node.id in blessed:
            return True
        return False

    # -- hazard detection --------------------------------------------------

    def _shape_hazards(
        self, arg: ast.AST, blessed: Set[str]
    ) -> Iterable[Tuple[str, int]]:
        for node in ast.walk(arg):
            if isinstance(node, ast.Slice):
                for bound in (node.lower, node.upper):
                    if bound is None or self._static_or_blessed(bound, blessed):
                        continue
                    yield (
                        f"slice bound `{ast.unparse(bound)}` is a runtime "
                        "value, so the sliced shape recompiles per value",
                        getattr(bound, "lineno", getattr(arg, "lineno", 0)),
                    )
            elif isinstance(node, ast.Call):
                ctor = dotted_name(node.func)
                if ctor.split(".")[-1] in ("zeros", "full", "empty", "ones"):
                    shape = node.args[0] if node.args else None
                    if shape is not None and not self._static_or_blessed(
                        shape, blessed
                    ):
                        yield (
                            f"array constructed with runtime shape "
                            f"`{ast.unparse(shape)}`",
                            node.lineno,
                        )

    def _static_or_blessed(self, node: ast.AST, blessed: Set[str]) -> bool:
        """A shape expression is static if its every leaf is a constant, a
        blessed name (derived from a bucketing helper), or an attribute
        chain rooted at ``self`` (per-engine config) or a blessed name."""
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            return node.id in blessed or node.id == "self"
        if isinstance(node, (ast.Attribute, ast.Subscript)):
            root: ast.AST = node
            while isinstance(root, (ast.Attribute, ast.Subscript)):
                root = root.value
            return isinstance(root, ast.Name) and (
                root.id in blessed or root.id == "self"
            )
        if isinstance(node, ast.BinOp):
            return self._static_or_blessed(
                node.left, blessed
            ) and self._static_or_blessed(node.right, blessed)
        if isinstance(node, ast.UnaryOp):
            return self._static_or_blessed(node.operand, blessed)
        if isinstance(node, (ast.Tuple, ast.List)):
            return all(self._static_or_blessed(e, blessed) for e in node.elts)
        if isinstance(node, ast.Call):
            callee = dotted_name(node.func).split(".")[-1]
            if callee in BUCKETING_HELPERS:
                return True
            if callee in ("len", "min", "max", "int"):
                return all(
                    self._static_or_blessed(a, blessed) for a in node.args
                )
            return False
        return False


class ObsDisciplineRule(Rule):
    """Two disciplines from the observability layer (PR 10):

    (a) metrics migrated onto the registry are mutated ONLY through their
        registry instruments — writes to the legacy attribute names (now
        read-only views) or to ``*.perf.<field>`` fork the bookkeeping;
    (b) ``SpanRecorder`` mutators (``*.obs.begin/end/instant/terminal``)
        are called only at the lifecycle points declared in SPAN_SCOPES,
        and ``begin`` names its span by a literal in SPAN_NAMES.
    """

    id = "obs-discipline"
    paths = ("serving/*.py", "core/*.py")

    def check(self, mod: ModuleInfo) -> Iterable[Finding]:
        yield from self._check_metric_writes(mod)
        yield from self._check_span_sites(mod)

    def _check_metric_writes(self, mod: ModuleInfo) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign):
                targets: List[ast.AST] = list(node.targets)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            else:
                continue
            scope = mod.scope(node)
            for t in targets:
                for root in _iter_target_roots(t):
                    while isinstance(root, (ast.Subscript, ast.Starred)):
                        root = root.value
                    if not isinstance(root, ast.Attribute):
                        continue
                    owner = root.value
                    owner_attr = (owner.attr
                                  if isinstance(owner, ast.Attribute) else None)
                    if root.attr in MIGRATED_METRICS:
                        yield Finding(
                            rule=self.id, path=mod.relpath, line=node.lineno,
                            scope=scope, call=root.attr,
                            arg=ast.unparse(t) if hasattr(ast, "unparse") else "",
                            message=(
                                f"write to `{root.attr}`, a metric migrated "
                                "to the repro.obs registry (the attribute is "
                                "a read-only view); mutate the registry "
                                "instrument instead"
                            ),
                        )
                    elif owner_attr == "perf":
                        yield Finding(
                            rule=self.id, path=mod.relpath, line=node.lineno,
                            scope=scope, call=f"perf.{root.attr}",
                            arg=ast.unparse(t) if hasattr(ast, "unparse") else "",
                            message=(
                                f"direct write to PerfCounters field "
                                f"`{root.attr}`; mutate via "
                                "perf.inc()/perf.max_update() so the registry "
                                "stays the single source of truth"
                            ),
                        )

    def _check_span_sites(self, mod: ModuleInfo) -> Iterable[Finding]:
        patterns: Tuple[str, ...] = ()
        for glob, pats in SPAN_SCOPES.items():
            if fnmatch.fnmatch(mod.relpath, glob):
                patterns = patterns + pats
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (isinstance(fn, ast.Attribute) and fn.attr in SPAN_METHODS):
                continue
            recv = fn.value
            if not (isinstance(recv, ast.Attribute) and recv.attr == "obs"):
                continue
            scope = mod.scope(node)
            if not _scope_matches(scope, patterns):
                yield Finding(
                    rule=self.id, path=mod.relpath, line=node.lineno,
                    scope=scope, call=f"obs.{fn.attr}",
                    arg=first_arg_src(node),
                    message=(
                        f"span recorder `{fn.attr}()` outside the declared "
                        "lifecycle scopes (rules.SPAN_SCOPES); spans "
                        "open/close only at declared request-lifecycle / "
                        "engine-phase points"
                    ),
                )
            elif fn.attr == "begin" and not (
                    node.args and isinstance(node.args[0], ast.Constant)
                    and node.args[0].value in SPAN_NAMES):
                yield Finding(
                    rule=self.id, path=mod.relpath, line=node.lineno,
                    scope=scope, call="obs.begin", arg=first_arg_src(node),
                    message=(
                        "span opened under a name that is not a literal in "
                        "repro.obs.spans.SPAN_NAMES; declare the name there "
                        "so trace readers can select it"
                    ),
                )


ALL_RULES: Tuple[Rule, ...] = (
    SyncPointRule(),
    EmitDisciplineRule(),
    ResidencyDisciplineRule(),
    JitHygieneRule(),
    RecompileHazardRule(),
    ObsDisciplineRule(),
)
