"""repro.analysis: rule-by-rule lint tests (known-bad snippets each rule
must flag, known-good dispatch-point code it must pass), allowlist
semantics, seeded-violation detection on a copy of the real package, CLI
exit codes, and the jaxpr auditor (clean run + seeded callback / captured
const / broken donation)."""
import shutil
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ALL_RULES, AllowEntry, load_allowlist, run_lint
from repro.analysis.lint import _parse_toml_minimal


def lint_snippet(tmp_path, relpath, code, allowlist=()):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    return run_lint(tmp_path, ALL_RULES, allowlist)


def rules_of(report):
    return [f.rule for f in report.findings]


# --------------------------------------------------------------------------
# sync-point
# --------------------------------------------------------------------------


def test_sync_point_flags_asarray_in_decode_step(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        import numpy as np
        class BatchedServingEngine:
            def _decode_step(self, batch):
                ids_np = np.asarray(self.ids)
                return ids_np
    """)
    assert rules_of(rep) == ["sync-point"]
    f = rep.findings[0]
    assert f.path == "serving/batching.py" and f.line == 5
    assert f.scope == "BatchedServingEngine._decode_step"
    assert f.call == "np.asarray"


@pytest.mark.parametrize("call", [
    "x.item()", "x.block_until_ready()", "x.tolist()",
    "jax.device_get(x)", "float(x)",
])
def test_sync_point_flags_every_sync_form(tmp_path, call):
    rep = lint_snippet(tmp_path, "serving/engine.py", f"""
        import jax
        class MoEServingEngine:
            def decode(self, x):
                return {call}
    """)
    assert rules_of(rep) == ["sync-point"]


def test_sync_point_ignores_cold_scopes(tmp_path):
    # same sync call, but in a scope that is not on the per-token path
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        import numpy as np
        class BatchedServingEngine:
            def snapshot(self, req):
                return np.asarray(self.thing)
        def helper(x):
            return np.asarray(x)
    """)
    assert rep.findings == []


def test_sync_point_ignores_host_literals(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        import numpy as np
        class BatchedServingEngine:
            def _decode_step(self, batch):
                a = np.asarray([r.pos for r in batch], np.int32)
                b = np.asarray([1, 2, 3])
                c = float("nan")
                return a, b, c
    """)
    assert rep.findings == []


def test_sync_point_scans_kernels_everywhere(tmp_path):
    rep = lint_snippet(tmp_path, "kernels/custom.py", """
        import numpy as np
        def my_kernel(x):
            return np.asarray(x)
    """)
    assert rules_of(rep) == ["sync-point"]


# --------------------------------------------------------------------------
# emit-discipline
# --------------------------------------------------------------------------


def test_emit_flags_token_event_outside_sink(tmp_path):
    rep = lint_snippet(tmp_path, "serving/frontend.py", """
        from repro.serving.api import TokenEvent
        class ServingFrontend:
            def poll(self, rid, tok):
                self.engine._emit(TokenEvent(rid=rid, token=tok, index=0,
                                             t=0.0))
    """)
    assert rules_of(rep) == ["emit-discipline"]
    assert rep.findings[0].call == "TokenEvent"


def test_emit_flags_raw_buffer_append(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            def _retire(self, ev):
                self._events.append(ev)
    """)
    assert rules_of(rep) == ["emit-discipline"]


def test_emit_allows_the_sinks(tmp_path):
    rep = lint_snippet(tmp_path, "serving/engine.py", """
        from repro.serving.api import TokenEvent
        class EngineCore:
            def _emit(self, ev):
                self._events.append(ev)
        class MoEServingEngine:
            def _emit_token(self, rid, token, index):
                self._emit(TokenEvent(rid=rid, token=token, index=index,
                                      t=0.0))
    """)
    assert rep.findings == []


# --------------------------------------------------------------------------
# residency-discipline
# --------------------------------------------------------------------------


def test_residency_flags_kv_write_outside_writers(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            def evil_helper(self, l, ck):
                self._K[l] = self._K[l].at[0].set(ck)
    """)
    assert rules_of(rep) == ["residency-discipline"]
    assert rep.findings[0].call == "_K"


def test_residency_flags_pool_write_outside_residency(tmp_path):
    rep = lint_snippet(tmp_path, "core/cache.py", """
        class SomethingElse:
            def poke(self, w):
                self._pools["w1"] = w
    """)
    assert rules_of(rep) == ["residency-discipline"]


def test_residency_allows_declared_writers(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            def _decode_step(self, l, ck, jidx):
                self._K[l] = self._K[l].at[jidx].set(ck)
            def restore(self, l, K, V):
                self._K[l], self._V[l] = K, V
            def _release_slot(self, req):
                self._slot_pos[req.slot, :] = -1
    """)
    assert rep.findings == []
    rep2 = lint_snippet(tmp_path / "b", "core/cache.py", """
        class ExpertResidency:
            def prefetch(self, name, val):
                self._pools[name] = val
    """)
    assert rep2.findings == []


# --------------------------------------------------------------------------
# jit-hygiene
# --------------------------------------------------------------------------


def test_jit_flags_jit_in_loop_body(tmp_path):
    rep = lint_snippet(tmp_path, "core/predictor.py", """
        import jax
        def train(xs):
            for x in xs:
                f = jax.jit(lambda a: a + 1)
                f(x)
    """)
    assert "jit-hygiene" in rules_of(rep)


def test_jit_flags_inline_invocation(tmp_path):
    rep = lint_snippet(tmp_path, "core/util.py", """
        import jax
        def apply(x):
            return jax.jit(lambda a: a * 2)(x)
    """)
    assert "jit-hygiene" in rules_of(rep)


def test_jit_flags_serving_method_jit(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        import jax
        class BatchedServingEngine:
            def step(self):
                self._f = jax.jit(self._raw)
    """)
    assert "jit-hygiene" in rules_of(rep)


def test_jit_flags_mutable_closure_capture(tmp_path):
    rep = lint_snippet(tmp_path, "serving/engine.py", """
        import jax
        class EngineCore:
            def _jit_fns(self):
                @jax.jit
                def bad(x):
                    return x + self.cache.capacity
                self._bad = bad
    """)
    assert "jit-hygiene" in rules_of(rep)
    assert "self.cache" in rep.findings[0].message


def test_jit_allows_setup_scope_and_module_level(tmp_path):
    rep = lint_snippet(tmp_path, "serving/engine.py", """
        import jax
        class EngineCore:
            def _jit_fns(self):
                @jax.jit
                def good(x):
                    return x * self.cfg.scale + self.E
                self._good = good
    """)
    assert rep.findings == []
    rep2 = lint_snippet(tmp_path / "b", "core/cache.py", """
        import functools, jax
        @functools.partial(jax.jit, donate_argnums=(0,))
        def _pool_write(pool, slot, slab):
            return pool.at[slot].set(slab)
    """)
    assert rep2.findings == []


def test_jit_allows_jit_before_loop(tmp_path):
    # the train_predictor pattern: define once, THEN loop
    rep = lint_snippet(tmp_path, "core/predictor.py", """
        import jax
        def train(xs):
            f = jax.jit(lambda a: a + 1)
            for x in xs:
                f(x)
    """)
    assert rep.findings == []


# --------------------------------------------------------------------------
# recompile-hazard
# --------------------------------------------------------------------------


def test_recompile_flags_raw_slice_bound(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            def _decode_step(self, xn, rows, n_live):
                return self._grouped_raw(xn[:n_live], rows)
    """)
    assert "recompile-hazard" in rules_of(rep)
    assert "n_live" in rep.findings[0].message


def test_recompile_flags_runtime_shape_ctor(tmp_path):
    rep = lint_snippet(tmp_path, "serving/engine.py", """
        import jax.numpy as jnp
        class EngineCore:
            def _run(self, xn, n):
                return self._expert_raw(jnp.zeros((n, 4)), xn)
    """)
    assert "recompile-hazard" in rules_of(rep)


def test_recompile_allows_bucketed_shapes(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            def _decode_step(self, xn, ids_np, union, B):
                C = _bucket(len(union), B)
                disp = group_by_expert(ids_np, union, bucket_cap=B)
                a = self._grouped_raw(xn[:C], disp.row_idx)
                b = self._grouped_raw(xn, disp.row_idx[:, :C])
                return a, b
    """)
    assert rep.findings == []


def test_recompile_allows_static_and_config_bounds(tmp_path):
    rep = lint_snippet(tmp_path, "serving/engine.py", """
        class EngineCore:
            def _run(self, xn):
                return self._expert_raw(xn[:, :4], xn[:, -1], xn[:, :self.k])
    """)
    assert rep.findings == []


# --------------------------------------------------------------------------
# obs-discipline
# --------------------------------------------------------------------------


def test_obs_flags_migrated_metric_write(tmp_path):
    rep = lint_snippet(tmp_path, "serving/cluster.py", """
        class ReplicaPool:
            def migrate(self, rid, src, dst):
                self.n_handoffs += 1
                return rid
    """)
    assert rules_of(rep) == ["obs-discipline"]
    f = rep.findings[0]
    assert f.call == "n_handoffs"
    assert "read-only view" in f.message


def test_obs_flags_subscript_write_to_migrated_metric(tmp_path):
    rep = lint_snippet(tmp_path, "serving/cluster.py", """
        class QosAutopilot:
            def scan(self, now, reason):
                self.by_reason[reason] += 1
    """)
    assert rules_of(rep) == ["obs-discipline"]
    assert rep.findings[0].call == "by_reason"


def test_obs_flags_perf_field_write(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            def _retire(self, r):
                self.perf.decode_layers = 0
    """)
    assert rules_of(rep) == ["obs-discipline"]
    f = rep.findings[0]
    assert f.call == "perf.decode_layers"
    assert "perf.inc" in f.message


def test_obs_flags_span_call_outside_declared_scope(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            def _some_helper(self, r):
                self.obs.instant("request.peeked", "lifecycle", rid=r.rid)
    """)
    assert "obs-discipline" in rules_of(rep)
    f = next(f for f in rep.findings if f.rule == "obs-discipline")
    assert f.call == "obs.instant"
    assert "SPAN_SCOPES" in f.message


def test_obs_allows_span_calls_in_declared_scopes(tmp_path):
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            def _retire(self, r):
                self.obs.terminal(r.rid, r.finish_reason)

            def submit_request(self, r):
                self.obs.instant("request.queued", "lifecycle", rid=r.rid)
    """)
    assert [f for f in rep.findings if f.rule == "obs-discipline"] == []


def test_obs_allows_the_engine_span_sites(tmp_path):
    """The leaf spans that name the host's time inside a chunk or step:
    copies and barriers in the residency, attention, routing and combine
    in both phases, sampling at the step boundary."""
    rep = lint_snippet(tmp_path, "core/cache.py", """
        class ExpertResidency:
            def prefetch(self, key):
                span = self.obs.begin("expert.fetch", lane="prefetch")
                self.obs.end(span)

            def wait(self, key):
                span = self.obs.begin("expert.wait", lane="prefetch")
                self.obs.end(span)
    """)
    rep2 = lint_snippet(tmp_path, "serving/engine.py", """
        class EngineCore:
            def prefill_chunks(self, chunks):
                at = self.obs.begin("attention", lane="prefill", layer=0)
                self.obs.end(at)

            def _prefill_moe(self, l, lp, x):
                rt = self.obs.begin("moe.route", lane="prefill", layer=l)
                self.obs.end(rt)
                ct = self.obs.begin("moe.combine", lane="prefill", layer=l)
                self.obs.end(ct)
    """)
    rep3 = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            def _decode_step(self, batch):
                at = self.obs.begin("attention", lane="decode")
                self.obs.end(at)
                rt = self.obs.begin("moe.route", lane="decode")
                self.obs.end(rt)
                st = self.obs.begin("sample", lane="decode")
                self.obs.end(st)

            def _run_prefill_chunks(self, work):
                st = self.obs.begin("sample", lane="prefill", rid=req.rid)
                self.obs.end(st)
    """)
    for r in (rep, rep2, rep3):
        assert [f for f in r.findings if f.rule == "obs-discipline"] == []


def test_obs_prefill_sweep_span_sites(tmp_path):
    """One prefill sweep carries several requests: the front end opens the
    one ``prefill.chunk`` parent (with its ``rids``) around the engine's
    multi-request sweep, which opens the per-layer leaves. The one-chunk
    form delegates to the sweep and opens no span of its own."""
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            def _run_prefill_chunks(self, work):
                pt = self.obs.begin("prefill.chunk", lane="prefill",
                                    rids=[r.rid for r, _ in work], tokens=8)
                self.obs.end(pt)
    """)
    rep2 = lint_snippet(tmp_path, "serving/engine.py", """
        class EngineCore:
            def prefill_chunks(self, chunks):
                at = self.obs.begin("attention", lane="prefill", layer=0)
                self.obs.end(at)
    """)
    for r in (rep, rep2):
        assert [f for f in r.findings if f.rule == "obs-discipline"] == []
    rep3 = lint_snippet(tmp_path, "serving/engine.py", """
        class EngineCore:
            def prefill_chunk(self, chunk, start, kc, vc, sp):
                at = self.obs.begin("attention", lane="prefill", layer=0)
                self.obs.end(at)
    """)
    f = next(f for f in rep3.findings if f.rule == "obs-discipline")
    assert f.scope == "EngineCore.prefill_chunk" and f.call == "obs.begin"


@pytest.mark.parametrize("name", ['"expert.copy"', "name"])
def test_obs_flags_a_span_name_outside_span_names(tmp_path, name):
    rep = lint_snippet(tmp_path, "core/cache.py", f"""
        class ExpertResidency:
            def prefetch(self, key, name=None):
                span = self.obs.begin({name}, lane="prefetch")
                self.obs.end(span)
    """)
    f = next(f for f in rep.findings if f.rule == "obs-discipline")
    assert f.call == "obs.begin" and "SPAN_NAMES" in f.message


def test_obs_allows_registry_mutation_and_view_reads(tmp_path):
    rep = lint_snippet(tmp_path, "serving/cluster.py", """
        class ReplicaPool:
            def migrate(self, rid, nbytes):
                self._c_handoffs.inc()
                self._c_handoff_bytes.inc(nbytes)
                total = self.n_handoffs + self.handoff_bytes  # reads are fine
                return total
    """)
    assert rep.findings == []


# --------------------------------------------------------------------------
# allowlist mechanics
# --------------------------------------------------------------------------


BAD_DECODE = """
    import numpy as np
    class BatchedServingEngine:
        def _decode_step(self, batch):
            ids_np = np.asarray(self.ids)
            extra = np.asarray(self.other)
            return ids_np, extra
"""


def test_allowlist_suppresses_only_the_pinned_arg(tmp_path):
    allow = [AllowEntry(rule="sync-point", reason="declared dispatch point",
                        path="serving/batching.py",
                        scope="BatchedServingEngine._decode_step",
                        call="np.asarray", arg="self.ids")]
    rep = lint_snippet(tmp_path, "serving/batching.py", BAD_DECODE, allow)
    # the pinned arg is suppressed; the NEW sync in the same scope is not
    assert len(rep.findings) == 1
    assert rep.findings[0].arg == "self.other"
    assert len(rep.suppressed) == 1
    assert rep.unused_allows == []


def test_allowlist_reports_unused_entries(tmp_path):
    allow = [AllowEntry(rule="sync-point", reason="stale",
                        path="serving/engine.py", scope="Gone.method")]
    rep = lint_snippet(tmp_path, "serving/batching.py", """
        class BatchedServingEngine:
            pass
    """, allow)
    assert rep.unused_allows == allow


def test_minimal_toml_parser_roundtrip(tmp_path):
    text = """
    # comment
    [[allow]]
    rule = "sync-point"
    path = "serving/engine.py"
    scope = "EngineCore._sample"
    call = "np.asarray"
    arg = "logits"
    reason = "has \\"quotes\\" and a # hash"
    [[allow]]
    rule = "emit-discipline"
    reason = "second entry"
    """
    data = _parse_toml_minimal(textwrap.dedent(text))
    assert len(data["allow"]) == 2
    assert data["allow"][0]["scope"] == "EngineCore._sample"
    assert "# hash" in data["allow"][0]["reason"]
    p = tmp_path / "a.toml"
    p.write_text(textwrap.dedent(text))
    entries = load_allowlist(p)
    assert entries[0].arg == "logits" and entries[1].rule == "emit-discipline"


def test_allowlist_rejects_unknown_keys(tmp_path):
    p = tmp_path / "a.toml"
    p.write_text('[[allow]]\nrule = "sync-point"\nreason = "x"\nfile = "y"\n')
    with pytest.raises(ValueError, match="unknown keys"):
        load_allowlist(p)


# --------------------------------------------------------------------------
# the real package: clean baseline + seeded violations
# --------------------------------------------------------------------------


def _package_root() -> Path:
    import repro

    return Path(next(iter(repro.__path__)))


def _real_allowlist():
    return load_allowlist(_package_root() / "analysis" / "allowlist.toml")


def test_repo_lints_clean():
    rep = run_lint(_package_root(), ALL_RULES, _real_allowlist())
    assert rep.findings == [], "\n".join(f.format() for f in rep.findings)
    assert rep.unused_allows == []


def _copy_package(tmp_path) -> Path:
    dst = tmp_path / "repro"
    shutil.copytree(_package_root(), dst)
    return dst


def test_seeded_sync_in_decode_step_is_caught(tmp_path):
    root = _copy_package(tmp_path)
    f = root / "serving" / "batching.py"
    src = f.read_text()
    anchor = "ids_np = np.asarray(ids).reshape(B, self.k)"
    assert anchor in src
    f.write_text(src.replace(
        anchor, anchor + "\n            _stall = np.asarray(x)", 1))
    rep = run_lint(root, ALL_RULES, _real_allowlist())
    assert len(rep.findings) == 1
    found = rep.findings[0]
    assert found.rule == "sync-point"
    assert found.path == "serving/batching.py"
    assert found.scope == "BatchedServingEngine._decode_step"
    assert found.arg == "x" and found.line > 0


def test_seeded_unbucketed_shape_is_caught(tmp_path):
    root = _copy_package(tmp_path)
    f = root / "serving" / "engine.py"
    src = f.read_text()
    anchor = "return self._grouped_raw(xn, jrows, *self.cache.pools, jslots)"
    assert anchor in src
    f.write_text(src.replace(
        anchor,
        "return self._grouped_raw(xn, jrows[:len(union)], "
        "*self.cache.pools, jslots)", 1))
    rep = run_lint(root, ALL_RULES, _real_allowlist())
    assert [f.rule for f in rep.findings] == ["recompile-hazard"]
    assert rep.findings[0].path == "serving/engine.py"


def test_seeded_kv_write_is_caught(tmp_path):
    root = _copy_package(tmp_path)
    f = root / "serving" / "batching.py"
    src = f.read_text()
    anchor = "def _sample_req(self, r"
    assert anchor in src
    idx = src.index(anchor)
    line_end = src.index("\n", src.index(":", idx))
    body_insert = "\n        self._K[0] = None"
    f.write_text(src[:line_end] + body_insert + src[line_end:])
    rep = run_lint(root, ALL_RULES, _real_allowlist())
    assert "residency-discipline" in [x.rule for x in rep.findings]


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------


def test_cli_exit_codes(tmp_path, capsys):
    from repro.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    # real package, lint only: clean
    assert main(["--no-jaxpr"]) == 0
    # seeded violation: nonzero + rule id + file:line in output
    root = _copy_package(tmp_path)
    f = root / "serving" / "batching.py"
    src = f.read_text()
    anchor = "ids_np = np.asarray(ids).reshape(B, self.k)"
    f.write_text(src.replace(
        anchor, anchor + "\n            _stall = np.asarray(x)", 1))
    capsys.readouterr()
    code = main(["--no-jaxpr", "--root", str(root),
                 "--allowlist", str(root / "analysis" / "allowlist.toml")])
    out = capsys.readouterr().out
    assert code == 1
    assert "sync-point" in out and "serving/batching.py:" in out


# --------------------------------------------------------------------------
# jaxpr audit
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def audit_engine():
    from repro.analysis.jaxpr_audit import build_audit_engine

    return build_audit_engine()


def test_jaxpr_audit_clean(audit_engine):
    from repro.analysis.jaxpr_audit import run_audit

    rep = run_audit(eng=audit_engine)
    assert rep.ok, "\n".join(f.format() for f in rep.findings)
    assert rep.compile_keys <= rep.compile_key_bound
    assert len(rep.kernels) >= 14


def test_jaxpr_audit_flags_host_callback(audit_engine):
    from repro.analysis.jaxpr_audit import KernelSpec, audit_kernel

    def leaky(x):
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    spec = KernelSpec("leaky", jax.jit(leaky),
                      (jax.ShapeDtypeStruct((4,), jnp.float32),))
    findings = audit_kernel(spec)
    assert any(f.rule == "jaxpr-callback" for f in findings)


def test_jaxpr_audit_flags_captured_weight(audit_engine):
    from repro.analysis.jaxpr_audit import KernelSpec, audit_kernel

    big = jnp.ones((256, 256), jnp.float32)  # 256 KiB > limit

    spec = KernelSpec("capturer", jax.jit(lambda x: x @ big),
                      (jax.ShapeDtypeStruct((2, 256), jnp.float32),))
    findings = audit_kernel(spec)
    assert any(f.rule == "jaxpr-const" for f in findings)


def test_jaxpr_audit_flags_missing_donation(audit_engine):
    from repro.analysis.jaxpr_audit import KernelSpec, audit_kernel

    copying = jax.jit(lambda pool, v: pool.at[0].set(v))  # no donate_argnums
    spec = KernelSpec(
        "copying_write", copying,
        (jax.ShapeDtypeStruct((64, 1024), jnp.float32),
         jax.ShapeDtypeStruct((1024,), jnp.float32)),
        donate=(0,))
    findings = audit_kernel(spec)
    assert any(f.rule == "jaxpr-donation" for f in findings)


def test_compile_key_enumeration_matches_measurement(audit_engine):
    from repro.analysis.jaxpr_audit import (compile_key_bound,
                                            enumerate_grouped_keys,
                                            measure_grouped_keys)

    eng = audit_engine
    keys = enumerate_grouped_keys(eng.max_batch, eng.E, eng.k)
    measured = measure_grouped_keys(eng.max_batch, eng.E, eng.k)
    assert measured <= keys
    assert len(keys) <= compile_key_bound(eng.max_batch, eng.E, eng.k)
    # every padded dim the buckets produce is a power of two (or the clamp)
    for (B, U, C) in keys:
        assert U & (U - 1) == 0 or U == min(eng.E, B * eng.k)
        assert C & (C - 1) == 0 or C == B
