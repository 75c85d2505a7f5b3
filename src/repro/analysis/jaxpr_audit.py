"""Layer 2: jaxpr audit of every registered jitted kernel.

Traces each kernel with abstract shapes (``jax.make_jaxpr`` — no
compilation, no device work) and statically checks the properties the
benchmarks otherwise only observe dynamically:

* **jaxpr-callback** — no host callbacks / infeed / outfeed inside any
  kernel: a hidden host round-trip on the decode path is exactly the stall
  DuoServe's prefetch overlap exists to avoid.
* **jaxpr-const** — no oversized captured constants: a jitted closure that
  captures a weight array duplicates it in device memory *outside* the
  ExpertResidency ledger, silently breaking the capacity*bytes_per_expert
  HBM bound.
* **jaxpr-donation** — declared donations actually lower to aliased
  buffers (``_pool_write`` must update the pool in place, not copy it).
* **compile-keys** — enumerate the grouped-FFN compile-cache keys across
  every feasible (B, U, max-group-size) of a serving sweep, through the
  *real* ``group_by_expert`` bucketing, and assert the distinct-key count
  satisfies the O(log B)·O(log U) claim per batch size.

Run via ``python -m repro.analysis``; ``run_audit(extra=...)`` lets tests
register deliberately-bad kernels and assert they are flagged.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# a captured const larger than this is treated as an accidentally-baked-in
# weight (the embed table of even the reduced config is ~0.5 MiB; genuine
# scalars/masks are a few hundred bytes)
CONST_BYTES_LIMIT = 64 * 1024

# substrings of primitive names that mean "host round-trip"
CALLBACK_PRIMS = ("callback", "infeed", "outfeed", "host_local")


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    rule: str      # jaxpr-callback | jaxpr-const | jaxpr-donation | compile-keys
    kernel: str
    message: str

    def format(self) -> str:
        return f"{self.rule:<22} kernel:{self.kernel}  {self.message}"


@dataclasses.dataclass
class KernelSpec:
    """One registered jitted kernel: a callable plus example abstract args.

    ``donate`` lists argnums whose buffers the kernel declares donated —
    the audit verifies the lowering actually aliases them."""
    name: str
    fn: Callable
    args: Tuple
    donate: Tuple[int, ...] = ()


@dataclasses.dataclass
class AuditReport:
    findings: List[AuditFinding]
    kernels: List[str]
    compile_keys: int
    compile_key_bound: int

    @property
    def ok(self) -> bool:
        return not self.findings


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------


def _iter_eqns(jaxpr):
    """Yield every eqn in a jaxpr, recursing into sub-jaxprs (pjit bodies,
    scan/while/cond branches, pallas kernels).  Duck-typed so it works
    across jax versions: anything with ``.eqns`` is a jaxpr, anything with
    ``.jaxpr`` is a closed jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _sub_jaxprs(v):
    if hasattr(v, "eqns"):
        yield v
    elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
        yield v.jaxpr
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def _check_callbacks(name: str, closed) -> List[AuditFinding]:
    out = []
    for eqn in _iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if any(s in prim for s in CALLBACK_PRIMS):
            out.append(AuditFinding(
                "jaxpr-callback", name,
                f"primitive `{prim}` is a host round-trip inside a jitted "
                "kernel — a synchronization the dispatch-point discipline "
                "does not account for",
            ))
    return out


def _all_consts(closed):
    """Consts of a closed jaxpr AND of every nested closed jaxpr (a
    ``jax.jit`` wrapper hides closure captures inside the pjit eqn's
    sub-jaxpr)."""
    seen = [closed]
    consts = list(closed.consts)
    stack = [closed.jaxpr]
    while stack:
        jaxpr = stack.pop()
        for eqn in jaxpr.eqns:
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    stack.append(sub)
            for v in eqn.params.values():
                if hasattr(v, "consts") and hasattr(v, "jaxpr") and v not in seen:
                    seen.append(v)
                    consts.extend(v.consts)
    return consts


def _check_consts(name: str, closed) -> List[AuditFinding]:
    out = []
    for c in _all_consts(closed):
        nbytes = getattr(c, "nbytes", None)
        if nbytes is None:
            size = getattr(c, "size", 0)
            itemsize = getattr(getattr(c, "dtype", None), "itemsize", 1)
            nbytes = int(size) * int(itemsize)
        if nbytes > CONST_BYTES_LIMIT:
            shape = tuple(getattr(c, "shape", ()))
            out.append(AuditFinding(
                "jaxpr-const", name,
                f"captured constant of {nbytes} bytes (shape {shape}) baked "
                "into the trace — device memory outside the residency "
                "ledger; pass it as an argument instead",
            ))
    return out


def _check_donation(spec: KernelSpec) -> List[AuditFinding]:
    if not spec.donate:
        return []
    try:
        text = spec.fn.lower(*spec.args).as_text()
    except Exception as e:  # pragma: no cover - lowering failure is a finding
        return [AuditFinding(
            "jaxpr-donation", spec.name, f"could not lower to check donation: {e}"
        )]
    # donation lowers to `tf.aliasing_output` or `jax.buffer_donor`
    if "aliasing_output" not in text and "buffer_donor" not in text:
        return [AuditFinding(
            "jaxpr-donation", spec.name,
            f"declared donation of argnums {spec.donate} is not honored in "
            "the lowering (no aliasing_output/buffer_donor attribute): the "
            "kernel copies instead of updating in place",
        )]
    return []


def audit_kernel(spec: KernelSpec) -> List[AuditFinding]:
    try:
        closed = jax.make_jaxpr(spec.fn)(*spec.args)
    except Exception as e:
        return [AuditFinding(
            "jaxpr-callback", spec.name, f"kernel failed to trace: {e}"
        )]
    findings = _check_callbacks(spec.name, closed)
    findings += _check_consts(spec.name, closed)
    findings += _check_donation(spec)
    return findings


# --------------------------------------------------------------------------
# compile-key enumeration for the grouped FFN
# --------------------------------------------------------------------------


def _pow2_values(cap: int) -> Set[int]:
    """The set ``{_bucket(n, cap) : 1 <= n <= cap}`` — all padded sizes the
    bucketing can produce.  |set| <= floor(log2 cap) + 2."""
    from repro.serving.engine import _bucket

    return {_bucket(n, cap) for n in range(1, cap + 1)}


def enumerate_grouped_keys(max_batch: int, E: int, k: int) -> Set[Tuple[int, int, int]]:
    """Every (B, U_pad, C) compile key the decode grouped FFN can see,
    derived from the engine's own bucketing helpers."""
    keys: Set[Tuple[int, int, int]] = set()
    for B in range(1, max_batch + 1):
        ucap = min(E, B * k)
        for u_pad in _pow2_values(ucap):
            for c in _pow2_values(B):
                keys.add((B, u_pad, c))
    return keys


def compile_key_bound(max_batch: int, E: int, k: int) -> int:
    """The O(log B)·O(log U) bound the paper-claim reduces to: per batch
    size, at most (log2 B + 2) group capacities x (log2 Ucap + 2) group
    counts."""
    total = 0
    for B in range(1, max_batch + 1):
        ucap = min(E, B * k)
        total += (int(math.log2(B)) + 2) * (int(math.log2(ucap)) + 2)
    return total


def _sample_selection_patterns(B: int, E: int, k: int):
    """A deterministic battery of [B, k] expert-selection matrices spanning
    the shape-relevant extremes: fully clustered (one group of size B),
    fully spread (max distinct experts), and cyclic mixes in between."""
    pats = []
    # fully clustered: every row picks the same k experts -> U = k, count = B
    pats.append(np.tile(np.arange(k, dtype=np.int32), (B, 1)))
    # fully spread: rows walk distinct experts -> U = min(E, B*k)
    spread = (np.arange(B * k, dtype=np.int32).reshape(B, k)) % E
    pats.append(spread)
    # cyclic strides in between
    for stride in (1, 2, 3):
        ids = np.zeros((B, k), np.int32)
        for t in range(B):
            base = (t * stride) % E
            ids[t] = [(base + j) % E for j in range(k)]
        pats.append(ids)
    return pats


def measure_grouped_keys(max_batch: int, E: int, k: int) -> Set[Tuple[int, int, int]]:
    """Push the pattern battery through the REAL ``group_by_expert`` with
    the decode call site's caps and collect the resulting compile keys."""
    from repro.serving.engine import group_by_expert

    seen: Set[Tuple[int, int, int]] = set()
    for B in range(1, max_batch + 1):
        for ids in _sample_selection_patterns(B, E, k):
            union = list(dict.fromkeys(int(e) for e in ids.ravel()))
            disp = group_by_expert(ids, union, bucket_cap=B,
                                   u_bucket_cap=min(E, B * k))
            seen.add((B,) + disp.row_idx.shape)
    return seen


def audit_compile_keys(eng) -> Tuple[List[AuditFinding], int, int]:
    """Statically verify the recompile claim for the grouped decode FFN:
    (1) the enumerated key set respects the per-B logarithmic bound, and
    (2) every key produced by real selection patterns is in the enumerated
    set, and `_grouped_raw` traces at each one (same jit cache keys)."""
    findings: List[AuditFinding] = []
    B_max, E, k = eng.max_batch, eng.E, eng.k
    keys = enumerate_grouped_keys(B_max, E, k)
    bound = compile_key_bound(B_max, E, k)
    if len(keys) > bound:
        findings.append(AuditFinding(
            "compile-keys", "_grouped_raw",
            f"enumerated {len(keys)} grouped-FFN compile keys across "
            f"B=1..{B_max}, exceeding the O(log B)·O(log U) bound {bound} — "
            "a shape dimension is crossing the jit boundary unbucketed",
        ))
    measured = measure_grouped_keys(B_max, E, k)
    stray = measured - keys
    if stray:
        findings.append(AuditFinding(
            "compile-keys", "_grouped_raw",
            f"real selection patterns produced compile keys {sorted(stray)} "
            "outside the enumerated bucket set: group_by_expert's padding "
            "no longer matches the declared bucketing",
        ))
    # trace the kernel at every measured key: these are exactly the jit
    # cache entries a serving sweep can create
    d = eng.cfg.d_model
    pools = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in eng.cache.pools]
    xdt = eng.dev["embed"].dtype
    for (B, U, C) in sorted(measured):
        spec = KernelSpec(
            name=f"_grouped_raw[B={B},U={U},C={C}]",
            fn=eng._grouped_raw,
            args=(
                jax.ShapeDtypeStruct((B, 1, d), xdt),
                jax.ShapeDtypeStruct((U, C), jnp.int32),
                *pools,
                jax.ShapeDtypeStruct((U,), jnp.int32),
            ),
        )
        findings += audit_kernel(spec)
    return findings, len(measured), bound


# --------------------------------------------------------------------------
# kernel registry
# --------------------------------------------------------------------------


def build_audit_engine():
    """A reduced-config batched engine purely for tracing: construction
    initializes params and the jitted kernels but compiles nothing."""
    from repro.configs.base import get_config, reduced
    from repro.models.model import build
    from repro.serving.batching import BatchedServingEngine

    cfg = reduced(get_config("mixtral_8x7b"))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return BatchedServingEngine(cfg, params, policy="duo", max_batch=8,
                                max_seq=32, temperature=0.0)


def registered_kernels(eng) -> List[KernelSpec]:
    from repro.core.cache import _pool_write
    from repro.kernels.expert_ffn import expert_ffn, expert_ffn_from_pool

    cfg = eng.cfg
    d = cfg.d_model
    hkv, hd = cfg.n_kv_heads, cfg.hd
    W = eng.W
    B = 4
    lp = eng._layer(0)
    md = eng._moe_dev(0)
    xdt = eng.dev["embed"].dtype
    pools = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in eng.cache.pools]
    pdt = pools[0].dtype
    de = pools[0].shape[2]
    cap = pools[0].shape[0]
    S = jax.ShapeDtypeStruct
    i32 = jnp.int32

    kv = S((1, W, hkv, hd), xdt)
    kvB = S((B, W, hkv, hd), xdt)

    specs = [
        KernelSpec("attn_prefill", eng._attn_prefill,
                   (lp, S((1, 8, d), xdt))),
        KernelSpec("attn_prefill_chunk", eng._attn_prefill_chunk,
                   (lp, S((1, 4, d), xdt), kv, kv, S((1, W), i32),
                    S((), i32))),
        KernelSpec("attn_decode", eng._attn_decode,
                   (lp, S((1, 1, d), xdt), kv, kv, S((W,), i32),
                    S((), i32), S((), i32))),
        KernelSpec("attn_decode_batched", eng._attn_decode_batched,
                   (lp, S((B, 1, d), xdt), kvB, kvB, S((B, W), i32),
                    S((B,), i32), S((B,), i32))),
        KernelSpec("gate", eng._gate, (md, lp, S((B, 1, d), xdt))),
        KernelSpec("expert_raw", eng._expert_raw,
                   (S((B, 1, d), xdt), *pools, S((), i32))),
        KernelSpec("expert_apply", eng._expert,
                   (S((B, 1, d), xdt), *pools, S((), i32),
                    S((B,), jnp.float32))),
        KernelSpec("shared_apply", eng._shared, (md, S((B, 1, d), xdt))),
        KernelSpec("head", eng._head,
                   (eng.dev["ln_f"], eng.dev["embed"], S((B, d), xdt))),
        KernelSpec("expert_ffn[pallas]",
                   lambda x, w1, w3, w2: expert_ffn(
                       x, w1, w3, w2, block_f=de, interpret=True),
                   (S((2, 4, d), pdt), S((2, d, de), pdt),
                    S((2, d, de), pdt), S((2, de, d), pdt))),
        KernelSpec("expert_ffn_from_pool[pallas]",
                   lambda x, w1p, w3p, w2p, slots: expert_ffn_from_pool(
                       x, w1p, w3p, w2p, slots, interpret=True),
                   (S((2, 4, d), pdt), *pools, S((2,), i32))),
        KernelSpec("pool_write", _pool_write,
                   (S((cap, d, de), pdt), S((), i32), S((d, de), pdt)),
                   donate=(0,)),
        KernelSpec("snapshot_gather", _snapshot_gather,
                   (kvB, S((), i32))),
        KernelSpec("snapshot_scatter", _snapshot_scatter,
                   (kvB, S((6, hkv, hd), xdt), S((), i32))),
    ]
    return specs


# The snapshot/restore KV movement (serving/batching.py restore) expressed
# as traced kernels: per-prefix-length P they compile once per *restore*
# (a handoff boundary), never per token — the audit pins them callback- and
# const-free like every other kernel.
@jax.jit
def _snapshot_gather(K, slot):
    return jax.lax.dynamic_index_in_dim(K, slot, keepdims=False)


@jax.jit
def _snapshot_scatter(K, vals, slot):
    return K.at[slot, : vals.shape[0]].set(vals)


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------


def run_audit(extra: Optional[Sequence[KernelSpec]] = None,
              eng=None) -> AuditReport:
    if eng is None:
        eng = build_audit_engine()
    specs = registered_kernels(eng)
    if extra:
        specs = specs + list(extra)
    findings: List[AuditFinding] = []
    for spec in specs:
        findings += audit_kernel(spec)
    key_findings, n_keys, bound = audit_compile_keys(eng)
    findings += key_findings
    return AuditReport(
        findings=findings,
        kernels=[s.name for s in specs],
        compile_keys=n_keys,
        compile_key_bound=bound,
    )
