"""Red fixtures: every analysis pass must flag its deliberately-broken
miniature program (ISSUE 3 acceptance — a pass that cannot fail cannot
guard anything). Each fixture is the smallest program exhibiting one
hazard: a donated-but-unaliasable buffer, an un-aliased scan carry, a
silent bf16→f32 upcast feeding a matmul, a host callback inside the
program, and a known collective schedule the extractor must count
exactly. The retrace differ is driven with two signatures of the same
program and must name the argument that changed.
"""

from __future__ import annotations

import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.analysis import (
    analyze_program,
    diff_trace_signatures,
    find_aval_shapes,
    run_program_passes,
)
from deepspeed_tpu.profiling.compile_telemetry import CompileTelemetry


def _dispatch(tel, name, fn, *args, **jit_kwargs):
    wrapped = tel.instrument(name, fn, **jit_kwargs)
    with warnings.catch_warnings():
        # the broken-donation fixtures intentionally trip jax's
        # "donated argument was not used" warning
        warnings.simplefilter("ignore")
        wrapped(*args)
    return wrapped


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------
def test_donation_red_unaliasable_buffer():
    """A donated buffer no output can alias (shape matches nothing) must be
    reported with its double-buffered bytes."""
    tel = CompileTelemetry()

    def f(big, x):
        return x * 2.0

    _dispatch(tel, "bad", f, jnp.ones((128, 128)), jnp.ones((4,)), donate_argnums=(0,))
    res = analyze_program("bad", tel.programs()["bad"], passes=["donation"])["donation"]
    assert not res.ok
    assert res.violations, "unhonored donation not reported"


def test_donation_red_unaliased_scan_carry():
    """A scan whose carry is returned at a different dtype than the donated
    input cannot alias it — the pass reports the double-buffer."""
    tel = CompileTelemetry()

    def f(carry, xs):
        def body(c, x):
            return c + x.astype(c.dtype), ()

        out, _ = jax.lax.scan(body, carry, xs)
        return out.astype(jnp.bfloat16)  # dtype change: no alias possible

    _dispatch(
        tel, "scan_carry", f,
        jnp.zeros((64, 64), jnp.float32), jnp.ones((4, 64, 64), jnp.float32),
        donate_argnums=(0,),
    )
    res = analyze_program(
        "scan_carry", tel.programs()["scan_carry"], passes=["donation"]
    )["donation"]
    assert not res.ok
    assert any(v.details.get("bytes", 0) >= 64 * 64 * 4 for v in res.violations) or \
        any("double-buffered" in v.message for v in res.violations)


def test_donation_green_aliased_state():
    tel = CompileTelemetry()

    def step(state):
        return jax.tree_util.tree_map(lambda a: a + 1.0, state)

    _dispatch(
        tel, "ok", step, {"w": jnp.ones((32, 32)), "m": jnp.ones((32, 32))},
        donate_argnums=(0,),
    )
    res = analyze_program("ok", tel.programs()["ok"], passes=["donation"])["donation"]
    assert res.ok
    assert res.summary["declared_donations"] == 2


def test_donation_min_bytes_demotes_small_buffers():
    tel = CompileTelemetry()

    def f(tiny, x):
        return x * 2.0

    _dispatch(tel, "tiny", f, jnp.ones((2,)), jnp.ones((4,)), donate_argnums=(0,))
    res = analyze_program(
        "tiny", tel.programs()["tiny"], passes=["donation"],
        config={"min_donation_bytes": 1024},
    )["donation"]
    # still reported, but below the byte threshold → warn, not error
    assert res.violations
    assert res.ok


# ---------------------------------------------------------------------------
# dtype promotion
# ---------------------------------------------------------------------------
def test_dtype_red_silent_f32_upcast_matmul():
    tel = CompileTelemetry()

    def f(w, x):
        return w.astype(jnp.float32) @ x.astype(jnp.float32)

    _dispatch(tel, "upcast", f, jnp.ones((8, 8), jnp.bfloat16), jnp.ones((8, 8), jnp.bfloat16))
    res = analyze_program(
        "upcast", tel.programs()["upcast"], passes=["dtype_promotion"]
    )["dtype_promotion"]
    assert not res.ok
    assert any("dot_general" in v.message for v in res.violations)


def test_dtype_red_upcast_inside_scan():
    """Taint must follow into control-flow bodies (the fused-accum scan is
    where a silent upcast would actually hide)."""
    tel = CompileTelemetry()

    def f(w, xs):
        def body(c, x):
            return c + (w.astype(jnp.float32) @ x.astype(jnp.float32)), ()

        out, _ = jax.lax.scan(body, jnp.zeros((8, 8), jnp.float32), xs)
        return out

    _dispatch(tel, "scan_upcast", f, jnp.ones((8, 8), jnp.bfloat16), jnp.ones((2, 8, 8), jnp.bfloat16))
    res = analyze_program(
        "scan_upcast", tel.programs()["scan_upcast"], passes=["dtype_promotion"]
    )["dtype_promotion"]
    assert not res.ok


def test_dtype_green_softmax_boundary():
    """Softmax-in-f32 followed by a downcast PV matmul is the sanctioned
    pattern — zero violations."""
    tel = CompileTelemetry()

    def attn(q, k, v):
        s = (q @ k.T).astype(jnp.float32)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return p @ v

    _dispatch(tel, "attn", attn, *[jnp.ones((8, 8), jnp.bfloat16)] * 3)
    res = analyze_program(
        "attn", tel.programs()["attn"], passes=["dtype_promotion"]
    )["dtype_promotion"]
    assert res.ok, [v.message for v in res.violations]


def test_dtype_green_master_weight_update():
    """The mixed-precision optimizer pattern (bf16 grads upcast to f32 for
    elementwise update math against f32 master) is allowlisted by
    construction: no matmul touches the upcast values."""
    tel = CompileTelemetry()

    def update(master, grad_bf16):
        g32 = grad_bf16.astype(jnp.float32)
        new_master = master - 0.1 * g32
        return new_master, new_master.astype(jnp.bfloat16)

    _dispatch(tel, "update", update, jnp.ones((16, 16), jnp.float32), jnp.ones((16, 16), jnp.bfloat16))
    res = analyze_program(
        "update", tel.programs()["update"], passes=["dtype_promotion"]
    )["dtype_promotion"]
    assert res.ok, [v.message for v in res.violations]


# ---------------------------------------------------------------------------
# host transfer
# ---------------------------------------------------------------------------
def test_host_transfer_red_pure_callback():
    tel = CompileTelemetry()

    def f(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a) * 2.0, jax.ShapeDtypeStruct((4,), jnp.float32), x
        )
        return y + 1.0

    _dispatch(tel, "cb", f, jnp.ones((4,)))
    res = analyze_program(
        "cb", tel.programs()["cb"], passes=["host_transfer"]
    )["host_transfer"]
    assert not res.ok
    assert any("pure_callback" in v.message for v in res.violations)


def test_host_transfer_green_pure_math():
    tel = CompileTelemetry()
    _dispatch(tel, "clean", lambda x: jnp.tanh(x) * 2.0, jnp.ones((16,)))
    res = analyze_program(
        "clean", tel.programs()["clean"], passes=["host_transfer"]
    )["host_transfer"]
    assert res.ok


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------
def test_collectives_extractor_counts_known_schedule(eight_devices):
    """A program with exactly one dp all-reduce of a known payload: the
    extractor must report op kind, count, and per-device bytes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    s = NamedSharding(mesh, P("dp"))
    tel = CompileTelemetry()

    def f(x):
        return x - jnp.mean(x)  # mean over the sharded axis → one all-reduce

    x = jax.device_put(jnp.arange(64.0).reshape(64, 1), NamedSharding(mesh, P("dp", None)))
    _dispatch(tel, "ar", f, x)
    res = analyze_program("ar", tel.programs()["ar"], passes=["collectives"])["collectives"]
    ops = res.summary["ops"]
    assert "all-reduce" in ops, res.summary
    assert ops["all-reduce"]["count"] >= 1
    assert res.summary["total_bytes"] >= 4  # ≥ one f32 scalar per device


def test_collectives_budget_gate(eight_devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    tel = CompileTelemetry()

    def f(x):
        return x - jnp.mean(x)

    x = jax.device_put(jnp.ones((64, 8)), NamedSharding(mesh, P("dp", None)))
    _dispatch(tel, "budget", f, x)
    res = analyze_program(
        "budget", tel.programs()["budget"], passes=["collectives"],
        config={"collective_budget_bytes": 0},
    )["collectives"]
    assert not res.ok
    assert "budget" in res.violations[0].message


# ---------------------------------------------------------------------------
# retrace-cause differ
# ---------------------------------------------------------------------------
def test_retrace_differ_names_offending_argument():
    tel = CompileTelemetry()
    f = tel.instrument("prog", lambda a, b: a + b)
    f(jnp.ones((4, 4)), jnp.ones((4, 4)))
    f(jnp.ones((8, 4)), jnp.ones((8, 4)))  # retrace: arg shapes changed
    f(jnp.ones((8, 4)), jnp.ones((8, 4), jnp.bfloat16))  # retrace: b's dtype
    log = tel.program_stats("prog").trace_log
    assert len(log) == 3
    first = diff_trace_signatures(log[0], log[1])
    assert first and all(d["reason"] == "shape" for d in first)
    second = diff_trace_signatures(log[1], log[2])
    assert len(second) == 1
    assert second[0]["reason"] == "dtype"
    assert "[1]" in second[0]["arg"]  # names argument b, not a

    # the report surfaces the same diffs under the program entry
    rep = run_program_passes(tel, programs=["prog"], passes=["host_transfer"])
    retraces = rep["programs"]["prog"]["retraces"]
    assert len(retraces) == 2
    assert retraces[1]["changed"][0]["reason"] == "dtype"


def test_report_aggregates_and_flags():
    """run_program_passes folds per-program results into totals the bench
    and the engines consume (donation_verified, collective bytes)."""
    tel = CompileTelemetry()
    _dispatch(tel, "good", lambda s: jax.tree_util.tree_map(lambda a: a * 2, s),
              {"w": jnp.ones((16, 16))}, donate_argnums=(0,))

    def bad(big, x):
        return x + 1

    _dispatch(tel, "bad", bad, jnp.ones((64, 64)), jnp.ones((4,)), donate_argnums=(0,))
    rep = run_program_passes(tel)
    assert rep["totals"]["programs"] == 2
    assert rep["totals"]["donation_verified"] is False
    assert rep["programs"]["good"]["passes"]["donation"]["ok"] is True
    assert rep["programs"]["bad"]["passes"]["donation"]["ok"] is False
    # never-dispatched programs are skipped by the default selection...
    tel.instrument("never_ran", lambda x: x)
    rep2 = run_program_passes(tel)
    assert "never_ran" not in rep2["programs"]
    # ...but an EXPLICIT request for an unanalyzable or unknown name must
    # surface as a counted failure, never as a clean "verified" report
    rep3 = run_program_passes(tel, programs=["never_ran", "no_such_prog"])
    assert rep3["programs"]["never_ran"]["error"]
    assert rep3["programs"]["no_such_prog"]["error"]
    assert rep3["totals"]["analysis_failures"] == 2
    assert rep3["totals"]["donation_verified"] is False
    # and a report that never ran the donation pass must not claim it:
    # None (indeterminate), not True — even when a requested program fails
    rep4 = run_program_passes(tel, programs=["good"], passes=["collectives"])
    assert rep4["totals"]["donation_verified"] is None
    rep5 = run_program_passes(tel, programs=["no_such_prog"], passes=["collectives"])
    assert rep5["totals"]["analysis_failures"] == 1
    assert rep5["totals"]["donation_verified"] is None


def test_raise_mode_trips_on_analysis_failure():
    """A typo'd pass name (or any artifact build error) must not silently
    disable verify=raise: analysis failures raise, not just violations."""
    import pytest

    from deepspeed_tpu.analysis import AnalysisError, raise_or_warn

    tel = CompileTelemetry()
    _dispatch(tel, "p", lambda x: x + 1, jnp.ones((4,)))
    rep = run_program_passes(tel, programs=["p"], passes=["donations"])  # typo
    assert rep["totals"]["analysis_failures"] == 1
    with pytest.raises(AnalysisError):
        raise_or_warn(rep, "raise")


def test_donation_pruned_partial_shortfall_reported():
    """With an unused (pruned) arg breaking the index mapping, a donated
    buffer that went unhonored must still surface — as a warn-severity
    'partially unverifiable' violation, never as a clean verified pass."""
    tel = CompileTelemetry()

    def f(big, unused, state):
        return big.astype(jnp.bfloat16), state + 1.0  # big cannot alias

    _dispatch(
        tel, "partial", f,
        jnp.ones((256, 256)), jnp.ones((8,)), jnp.ones((16,)),
        donate_argnums=(0, 2),
    )
    res = analyze_program(
        "partial", tel.programs()["partial"], passes=["donation"]
    )["donation"]
    assert "arg_pruning" in res.summary
    assert res.violations, "partial unhonored donation invisible under pruning"


def test_collective_bytes_async_start_equals_sync():
    """Async ``-start`` bundles carry (operands..., results...) tuple
    shapes; the extractor must count only the result half so sync and
    async lowerings of one program report identical byte totals."""
    from deepspeed_tpu.analysis.hlo import collect_collectives

    sync = '%ag = f32[64,256]{1,0} all-gather(f32[8,256]{1,0} %p), dimensions={0}\n'
    async_ = (
        '%ags = (f32[8,256]{1,0}, f32[64,256]{1,0}) all-gather-start(f32[8,256]{1,0} %p), dimensions={0}\n'
        '%agd = f32[64,256]{1,0} all-gather-done((f32[8,256]{1,0}, f32[64,256]{1,0}) %ags)\n'
    )
    s = collect_collectives(sync)["all-gather"]
    a = collect_collectives(async_)["all-gather"]
    assert s["count"] == a["count"] == 1
    assert s["bytes"] == a["bytes"] == 64 * 256 * 4


def test_parse_computations_variadic_combined_async_start():
    """TPU's collective combiner emits variadic async starts whose bundle
    shape nests tuples two deep: ``((operands...), (results...))``. The
    instruction parser must not drop them — an unseen loop collective
    would let the overlap pass report a false overlap_verified: True —
    and the byte counter must count only the result half."""
    from deepspeed_tpu.analysis.hlo import instruction_bytes, parse_computations

    hlo = (
        "ENTRY %main (p0: f32[2,4]) -> f32[8,4] {\n"
        "  %p0 = f32[2,4]{1,0} parameter(0)\n"
        "  %ags = ((f32[2,4]{1,0}, f32[2,4]{1,0}), (f32[8,4]{1,0}, f32[8,4]{1,0}))"
        " all-gather-start(f32[2,4]{1,0} %p0, f32[2,4]{1,0} %p0), dimensions={0}\n"
        "  ROOT %agd = (f32[8,4]{1,0}, f32[8,4]{1,0}) all-gather-done(%ags)\n"
        "}\n"
    )
    comps, entry = parse_computations(hlo)
    ops = {i.name: i for i in comps[entry]}
    assert "ags" in ops, "variadic combined async start dropped by the parser"
    start = ops["ags"]
    assert start.op == "all-gather" and start.suffix == "-start"
    assert instruction_bytes(start) == 2 * 8 * 4 * 4  # results only


def test_async_start_context_scalars_not_counted_as_results():
    """collective-permute-start's bundle is ``(src, dest, u32[], u32[])`` —
    the trailing u32[] scalars are scheduler context, not payload. The
    even-split heuristic must not take them as the "result half" (that
    would report ~8 bytes for an N-element permute)."""
    from deepspeed_tpu.analysis.hlo import instruction_bytes, parse_computations

    hlo = (
        "ENTRY %main (p0: f32[64,32]) -> f32[64,32] {\n"
        "  %p0 = f32[64,32]{1,0} parameter(0)\n"
        "  %cps = (f32[64,32]{1,0}, f32[64,32]{1,0}, u32[], u32[])"
        " collective-permute-start(f32[64,32]{1,0} %p0),"
        " source_target_pairs={{0,1},{1,0}}\n"
        "  ROOT %cpd = f32[64,32]{1,0} collective-permute-done(%cps)\n"
        "}\n"
    )
    comps, entry = parse_computations(hlo)
    start = {i.name: i for i in comps[entry]}["cps"]
    assert start.op == "collective-permute" and start.suffix == "-start"
    assert instruction_bytes(start) == 64 * 32 * 4  # the dest payload only


def test_overlap_loop_membership_is_transitive():
    """An exposed collective in a computation *called from* a while body
    (here via ``call``/``to_apply`` — same shape as a cond branch or a
    nested scan) executes once per iteration, exactly like one written
    directly in the body. The overlap pass must treat it as a loop
    collective: if membership stopped at the body itself, this schedule
    would false-green as overlap_verified."""
    from deepspeed_tpu.analysis.passes import ProgramArtifact, overlap_pass

    hlo = (
        "%gather_and_dot (p: f32[8,64]) -> f32[64,64] {\n"
        "  %p = f32[8,64]{1,0} parameter(0)\n"
        "  %ag = f32[64,64]{1,0} all-gather(f32[8,64]{1,0} %p), dimensions={0}\n"
        "  ROOT %d = f32[64,64]{1,0} dot(f32[64,64]{1,0} %ag, f32[64,64]{1,0}"
        " %ag), lhs_contracting_dims={1}, rhs_contracting_dims={0}\n"
        "}\n"
        "%body (t: (s32[], f32[8,64])) -> (s32[], f32[8,64]) {\n"
        "  %t = (s32[], f32[8,64]{1,0}) parameter(0)\n"
        "  %i = s32[] get-tuple-element((s32[], f32[8,64]{1,0}) %t), index=0\n"
        "  %w = f32[8,64]{1,0} get-tuple-element((s32[], f32[8,64]{1,0}) %t), index=1\n"
        "  %c = f32[64,64]{1,0} call(f32[8,64]{1,0} %w), to_apply=%gather_and_dot\n"
        "  %sl = f32[8,64]{1,0} slice(f32[64,64]{1,0} %c), slice={[0:8], [0:64]}\n"
        "  %one = s32[] constant(1)\n"
        "  %ip = s32[] add(s32[] %i, s32[] %one)\n"
        "  ROOT %r = (s32[], f32[8,64]{1,0}) tuple(s32[] %ip, f32[8,64]{1,0} %sl)\n"
        "}\n"
        "%cond (t: (s32[], f32[8,64])) -> pred[] {\n"
        "  %t = (s32[], f32[8,64]{1,0}) parameter(0)\n"
        "  %i = s32[] get-tuple-element((s32[], f32[8,64]{1,0}) %t), index=0\n"
        "  %n = s32[] constant(4)\n"
        "  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT\n"
        "}\n"
        "ENTRY %main (p0: f32[8,64]) -> (s32[], f32[8,64]) {\n"
        "  %p0 = f32[8,64]{1,0} parameter(0)\n"
        "  %zero = s32[] constant(0)\n"
        "  %init = (s32[], f32[8,64]{1,0}) tuple(s32[] %zero, f32[8,64]{1,0} %p0)\n"
        "  ROOT %wh = (s32[], f32[8,64]{1,0}) while((s32[], f32[8,64]{1,0})"
        " %init), condition=%cond, body=%body\n"
        "}\n"
    )
    art = ProgramArtifact("fixture", wrapper=None)
    art._hlo_text = hlo
    res = overlap_pass(art)
    # the gather feeds the only dot, so nothing independent hides it...
    assert res.summary["exposed_count"] == 1, res.summary
    # ...and it sits one call level below the while body: still a loop
    # collective, so the program must NOT verify
    assert res.summary["loop_collectives"] == 1, res.summary
    assert res.summary["overlap_verified"] is False, res.summary
    assert res.violations
    assert res.violations[0].details["computation"] == "gather_and_dot"


# ---------------------------------------------------------------------------
# green sweep: speculative verify programs (ISSUE 4)
# ---------------------------------------------------------------------------
def test_green_spec_verify_programs():
    """The serving programs of a server whose every decode round drafts (the
    narrow ragged width carries verify rows, the mixed one the chunks)
    verify clean under every pass: donated page buffers aliased, zero host
    transfers, zero upcast-compute sites, zero violations overall."""
    from deepspeed_tpu.analysis import run_program_passes
    from deepspeed_tpu.inference.scheduler import PagedServer
    from deepspeed_tpu.inference.spec_decode import Drafter
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    class TwoTokenDrafter(Drafter):
        # always drafts something: every decode row is a verify row
        def propose(self, uid, context, k):
            return np.asarray([0, 1][: max(k, 0)], np.int32)

    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=64, norm="rmsnorm", position="rope",
        activation="swiglu", use_bias=False, tie_embeddings=False,
        flash_attention=False, dtype="float32",
    )
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    tel = CompileTelemetry()
    server = PagedServer(
        cfg, params, page_size=8, max_slots=4, prefill_chunk=8,
        attn_impl="xla", dtype=jnp.float32, telemetry=tel,
        spec_decode={"max_draft": 2}, drafter=TwoTokenDrafter(),
    )
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 128, (7,)).astype(np.int32) for _ in range(3)]
    server.serve(prompts, max_new_tokens=4)
    assert server.stats["spec_rounds"] >= 1 and server.stats["decode_steps"] <= 1
    rep = run_program_passes(tel)
    names = set(rep["programs"])
    assert names == {"paged_ragged_r4_w3", "paged_ragged_r4_w8"}, names
    t = rep["totals"]
    assert t["analysis_failures"] == 0 and t["violations"] == 0, rep
    assert t["donation_verified"] is True
    for name in names:
        passes = rep["programs"][name]["passes"]
        assert passes["host_transfer"]["ok"]
        assert passes["dtype_promotion"]["ok"]
        assert passes["donation"]["ok"]


# ---------------------------------------------------------------------------
# green sweep: the production-traffic serving path (ISSUE 6) — prefix
# caching + multi-tenant scheduling must ride the SAME verified programs
# ---------------------------------------------------------------------------
def test_green_traffic_serving_programs():
    """Serving through the traffic layer (prefix-cached pool + SLA tenant
    scheduler) dispatches only the existing paged programs — donation
    aliased, zero host transfers, zero violations — and sharing adds no
    dispatches: one ragged dispatch a scheduler step, even with prefix
    attaches happening."""
    from deepspeed_tpu.analysis import run_program_passes
    from deepspeed_tpu.inference.scheduler import PagedServer
    from deepspeed_tpu.inference.traffic import MultiTenantServer, TenantSpec
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=64, norm="rmsnorm", position="rope",
        activation="swiglu", use_bias=False, tie_embeddings=False,
        flash_attention=False, dtype="float32",
    )
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    tel = CompileTelemetry()
    server = MultiTenantServer(
        PagedServer(
            cfg, params, page_size=8, max_slots=4, prefill_chunk=8,
            attn_impl="xla", dtype=jnp.float32, telemetry=tel,
            prefix_cache=True,
        ),
        tenants=[TenantSpec(name="a", weight=2.0), TenantSpec(name="b")],
    )
    rs = np.random.RandomState(0)
    sys_tokens = rs.randint(0, 128, (16,)).astype(np.int32)  # 2 full pages
    prompts = [
        np.concatenate([sys_tokens, rs.randint(0, 128, (3 + i,)).astype(np.int32)])
        for i in range(4)
    ]
    # the first serve publishes the shared pages, the second attaches them
    server.serve(prompts[:1], max_new_tokens=4, tenant="a")
    server.serve(prompts[1:], max_new_tokens=4, tenant=["b", "a", "b"])
    assert server.pool.stats["prefix_hit_pages"] > 0  # sharing engaged
    stats = tel.stats()
    assert sum(rec["dispatches"] for rec in stats.values()) == server.stats["ragged_steps"]
    assert server.stats["decode_steps"] >= 1 and server.stats["prefill_chunks"] >= 4
    assert all(n.startswith("paged_ragged_") for n in stats), stats.keys()
    rep = run_program_passes(tel)
    t = rep["totals"]
    assert t["analysis_failures"] == 0 and t["violations"] == 0, rep
    assert t["donation_verified"] is True
    for name, prog in rep["programs"].items():
        assert prog["passes"]["host_transfer"]["ok"], name
        assert prog["passes"]["donation"]["ok"], name


# ---------------------------------------------------------------------------
# green sweep + compile-budget gate: the ragged serving program (ISSUE 8)
# ---------------------------------------------------------------------------
def test_green_ragged_serving_program_and_compile_gate():
    """THE acceptance gate for ragged serving: a full mixed serve (prefill
    chunks + plain decode + drafted verify rows, the mix shifting across 3
    waves) compiles ≤ 2 ``paged_*`` programs TOTAL, dispatches exactly one
    ragged program per scheduler step, never retraces a program after its
    first compile (3-wave retrace guard), and every compiled ragged
    program verifies clean under the donation, host-transfer, and
    dtype-promotion passes."""
    from deepspeed_tpu.analysis import run_program_passes
    from deepspeed_tpu.inference.scheduler import (
        PagedServer,
        compiled_serving_programs,
    )
    from deepspeed_tpu.inference.spec_decode import Drafter
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    class MixDrafter(Drafter):
        # per-request spec-K mix: row uid drafts uid % 3 tokens, so rounds
        # carry 0-, 1-, and 2-draft rows simultaneously
        def propose(self, uid, context, k):
            return np.arange(min(k, uid % 3), dtype=np.int32)

    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=64, norm="rmsnorm", position="rope",
        activation="swiglu", use_bias=False, tie_embeddings=False,
        flash_attention=False, dtype="float32",
    )
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    tel = CompileTelemetry()
    server = PagedServer(
        cfg, params, page_size=8, max_slots=4, prefill_chunk=8,
        attn_impl="xla", dtype=jnp.float32, telemetry=tel,
        spec_decode={"max_draft": 2}, drafter=MixDrafter(), prefix_cache=True,
    )
    rs = np.random.RandomState(0)
    # 3 waves of shifting mixes: short prompts (single chunk), long prompts
    # (multi-chunk, so chunks ride WITH in-flight decoders), varying counts
    waves = [
        [rs.randint(0, 128, (int(n),)).astype(np.int32) for n in lens]
        for lens in ([5, 7], [19, 4, 22, 9], [13])
    ]
    compiles_after_wave = []
    for wave in waves:
        server.serve(wave, max_new_tokens=6)
        compiles_after_wave.append(
            sum(r["compiles"] for r in tel.stats().values())
        )
    assert server.stats["spec_rounds"] >= 1, "the mix never drafted"
    assert server.stats["prefill_chunks"] > len(
        [p for w in waves for p in w]
    ), "no multi-chunk prompt: prefill never coexisted with decode"
    stats = tel.stats()
    assert all(n.startswith("paged_ragged_") for n in stats), stats.keys()
    # THE gate: ≤ 2 compiled serving programs for the whole mixed serve
    assert compiled_serving_programs(stats) <= 2, stats
    # retrace guard: wave 1 compiled everything (warmup); waves 2 and 3
    # shifted the prefill/decode/verify mix without a single new trace
    assert compiles_after_wave[1] == compiles_after_wave[0], compiles_after_wave
    assert compiles_after_wave[2] == compiles_after_wave[0], compiles_after_wave
    for name, rec in stats.items():
        assert rec["compiles"] <= 1, f"{name} recompiled: {rec}"
    # exactly ONE dispatch per scheduler step
    assert sum(r["dispatches"] for r in stats.values()) == server.stats["ragged_steps"]
    # analysis green sweep: donation aliased, no host transfers, no upcasts
    rep = run_program_passes(tel)
    t = rep["totals"]
    assert t["analysis_failures"] == 0 and t["violations"] == 0, rep
    assert t["donation_verified"] is True
    for name in rep["programs"]:
        passes = rep["programs"][name]["passes"]
        assert passes["host_transfer"]["ok"]
        assert passes["dtype_promotion"]["ok"]
        assert passes["donation"]["ok"]


def test_green_tp_serving():
    """THE acceptance gate for multi-chip sharded serving (ISSUE 13): a
    full mixed serve (prefill chunks + decode + drafted verify rows, 3
    shifting waves) through a tp=4 tensor-parallel server with QUANTIZED
    all-reduces compiles ≤ 2 ``paged_*`` programs, dispatches exactly one
    sharded ragged program per scheduler step, never retraces, and every
    program verifies green under donation / host-transfer / dtype. The
    comm schedule is verified quantitatively: the int8 exchange's wire
    bytes are EXACTLY the fp tp=4 program's all-reduce wire bytes / 4 on
    the row-parallel projections (2·(g-1)/g·N int8 vs ·4N fp), equal to
    the analytic per-scan-body budget 2proj·2phase·(g-1)/g·R·W·H bytes, within a
    configured quantized budget, and every quantized loop collective is
    HIDDEN (``overlap_verified`` true — the chunked row matmul gives each
    exchange dependency-free MXU work)."""
    from deepspeed_tpu.analysis import run_program_passes
    from deepspeed_tpu.inference.scheduler import (
        PagedServer,
        compiled_serving_programs,
    )
    from deepspeed_tpu.inference.spec_decode import Drafter
    from deepspeed_tpu.inference.tp import TPServing, serving_mesh
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    class MixDrafter(Drafter):
        def propose(self, uid, context, k):
            return np.arange(min(k, uid % 3), dtype=np.int32)

    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=4, max_seq_len=64, norm="rmsnorm", position="rope",
        activation="swiglu", use_bias=False, tie_embeddings=False,
        flash_attention=False, dtype="float32",
    )
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    G = 4  # tp degree
    rs = np.random.RandomState(0)
    waves = [
        [rs.randint(0, 128, (int(n),)).astype(np.int32) for n in lens]
        for lens in ([5, 7], [19, 4, 22, 9], [13])
    ]

    def serve_all(quantized):
        tel = CompileTelemetry()
        tp = TPServing(mesh=serving_mesh(G), quantized_allreduce=quantized)
        server = PagedServer(
            cfg, params, page_size=8, max_slots=4, prefill_chunk=8,
            attn_impl="xla", dtype=jnp.float32, telemetry=tel,
            spec_decode={"max_draft": 2}, drafter=MixDrafter(),
            prefix_cache=True, tp=tp,
        )
        compiles = []
        outs = []
        for wave in waves:
            outs.append(server.serve(wave, max_new_tokens=6))
            compiles.append(sum(r["compiles"] for r in tel.stats().values()))
        return tel, server, compiles, outs

    telq, srvq, compiles_q, _ = serve_all(quantized=True)
    telf, srvf, _, _ = serve_all(quantized=False)
    assert srvq.stats["spec_rounds"] >= 1, "the mix never drafted"
    stats = telq.stats()
    assert all(n.startswith("paged_ragged_") for n in stats), stats.keys()
    # THE gate: ≤ 2 compiled serving programs, zero retraces, 1 dispatch/step
    assert compiled_serving_programs(stats) <= 2, stats
    assert compiles_q[1] == compiles_q[0] == compiles_q[2], compiles_q
    assert sum(r["dispatches"] for r in stats.values()) == srvq.stats["ragged_steps"]
    # green sweep on the QUANTIZED sharded programs
    rep = run_program_passes(telq)
    t = rep["totals"]
    assert t["analysis_failures"] == 0 and t["violations"] == 0, rep
    assert t["donation_verified"] is True
    for name, prog in rep["programs"].items():
        passes = prog["passes"]
        assert passes["host_transfer"]["ok"], name
        assert passes["dtype_promotion"]["ok"], name
        assert passes["donation"]["ok"], name
        # every quantized collective on the layer-scan hot path is HIDDEN
        ov = passes["overlap"]["summary"]
        assert ov["overlap_verified"] is True, (name, ov)
        assert ov["loop_quantized"] > 0, (name, ov)
        assert ov["loop_quantized_hidden"] == ov["loop_quantized"], (name, ov)
    # comm accounting: int8 exchange wire bytes == fp all-reduce wire / 4,
    # exactly — and exactly the analytic budget for the program's shape
    rep_f = run_program_passes(telf, passes=["collectives", "overlap"])
    wf = 2.0 * (G - 1) / G  # fp ring all-reduce wire factor
    for name, prog in rep["programs"].items():
        q = prog["passes"]["collectives"]["summary"]["quantized"]
        assert q["count"] > 0, name
        assert q["fp_equiv_wire_bytes"] == 4 * q["wire_bytes"], q
        fp_name = name.replace(f"_tp{G}q", f"_tp{G}")  # quantized -> fp build
        fp_sum = rep_f["programs"][fp_name]["passes"]["collectives"]["summary"]
        fp_ar_wire = int(round(fp_sum["ops"]["all-reduce"]["bytes"] * wf))
        assert fp_ar_wire == 4 * q["wire_bytes"], (name, fp_ar_wire, q)
        # analytic: 2 row-parallel projections × [R, W, H] int8 elements,
        # each moved twice at (g-1)/g (all-to-all + all-gather). The layer
        # scan's body appears ONCE in the static schedule — per-dispatch
        # wire cost is this × num_layers
        W = int(name.split("_w")[1].split("_")[0])
        R = 4  # max_slots: the ragged row budget
        analytic = int(round(2 * 2 * (G - 1) / G * R * W * cfg.hidden_size))
        assert q["wire_bytes"] == analytic, (name, q["wire_bytes"], analytic)
        # the fp program's row-parallel reductions are found where they are
        # made, in the layer scan's body: one all-reduce of [R, W, H] float32
        # a projection. (XLA's CPU pipeline combines a projection's
        # ``comm_chunks`` psums into one all-reduce fed by every chunk's
        # matmul, so the chunked schedule is not in the CPU text and
        # ``overlap_verified`` is no evidence for it here: the counts and
        # bytes are what a CPU compile establishes.)
        fp_ov = rep_f["programs"][fp_name]["passes"]["overlap"]["summary"]
        assert fp_ov["loop_collectives"] == 2 and fp_ov["loop_quantized"] == 0, (fp_name, fp_ov)
        assert [(e["op"], e["bytes"]) for e in fp_ov["loop_exposed"]] == [("all-reduce", R * W * cfg.hidden_size * 4)] * 2, fp_ov
    # the quantized-budget gate trips when configured below the schedule
    rep_bad = run_program_passes(
        telq, passes=["collectives"], config={"quantized_budget_bytes": 1}
    )
    assert any(
        not prog["passes"]["collectives"]["ok"]
        for prog in rep_bad["programs"].values()
    ), "quantized budget gate never fired"


def test_green_fleet_serving():
    """THE acceptance gate for fleet serving (ISSUE 12): a 3-replica
    fleet serving a shifting mix — including a chaos replica kill
    mid-serve — adds ZERO compiled programs beyond the single-replica
    ragged budget (≤ 2 ``paged_*`` programs TOTAL across every replica:
    uniform geometry + the shared program cache), never retraces after
    its first wave, keeps the ragged one-dispatch-per-step contract on
    every replica (dispatches/token unchanged vs a single replica —
    telemetry reconciles with the summed scheduler counters), the router
    itself is pure host code (lint DS-R010: no jax import in
    ``inference/fleet.py``), and every compiled program verifies clean
    under the donation / host-transfer / dtype passes."""
    from deepspeed_tpu.analysis import run_program_passes
    from deepspeed_tpu.analysis.source_lint import lint_paths
    from deepspeed_tpu.inference.fleet import FleetRouter, ReplicaHandle
    from deepspeed_tpu.inference.scheduler import (
        PagedServer,
        compiled_serving_programs,
    )
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig
    from deepspeed_tpu.utils import chaos as chaos_mod

    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=64, norm="rmsnorm", position="rope",
        activation="swiglu", use_bias=False, tie_embeddings=False,
        flash_attention=False, dtype="float32",
    )
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    tel = CompileTelemetry()

    def replica():
        return PagedServer(
            cfg, params, page_size=8, max_slots=4, prefill_chunk=8,
            attn_impl="xla", dtype=jnp.float32, telemetry=tel,
            prefix_cache=True,
        )

    router = FleetRouter(
        [ReplicaHandle(name=f"r{i}", server=replica()) for i in range(3)]
    )
    rs = np.random.RandomState(0)
    waves = [
        [rs.randint(0, 128, (int(n),)).astype(np.int32) for n in lens]
        for lens in ([5, 7, 11], [19, 4, 22, 9], [13, 6])
    ]
    compiles_after_wave = []
    for wi, wave in enumerate(waves):
        if wi == 1:
            # wave 2 serves across a replica kill: the survivors absorb
            # the dead replica's requests without a single new program
            chaos_mod.install(chaos_mod.ChaosSchedule(
                [chaos_mod.ChaosRule("fleet.replica_kill", hit=4)]
            ))
        try:
            outs = router.serve(wave, max_new_tokens=6)
        finally:
            chaos_mod.uninstall()
        assert all(o is not None for o in outs)
        compiles_after_wave.append(
            sum(r["compiles"] for r in tel.stats().values())
        )
    fs = router.fleet_stats()
    assert fs["replica_kills"] == 1, fs
    assert fs["n_active"] == 2
    assert fs["migrated_token_divergence"] == 0
    stats = tel.stats()
    assert all(n.startswith("paged_ragged_") for n in stats), stats.keys()
    # THE gate: the whole 3-replica fleet compiles no more programs than
    # one replica's ragged budget — replicas share the program cache
    assert compiled_serving_programs(stats) <= 2, stats
    # retrace guard: wave 1 compiled everything; the kill wave and the
    # recovery wave added nothing
    assert compiles_after_wave[1] == compiles_after_wave[0], compiles_after_wave
    assert compiles_after_wave[2] == compiles_after_wave[0], compiles_after_wave
    for name, rec in stats.items():
        assert rec["compiles"] <= 1, f"{name} recompiled: {rec}"
    # dispatches/token unchanged vs single replica: every replica still
    # runs ONE ragged dispatch per non-empty scheduler step, and the
    # fleet-summed telemetry reconciles exactly with the schedulers'
    # own dispatch counters (the router adds zero device work; the dead
    # replica's pre-kill dispatches stay in the merge)
    merged = router.serve_stats()
    inners = [h.inner for h in router.replicas.values()]
    assert sum(r["dispatches"] for r in stats.values()) == merged["dispatches"]
    assert merged["dispatches"] == sum(s.stats["dispatches"] for s in inners)
    assert merged["dispatches"] == sum(s.stats["ragged_steps"] for s in inners)
    # the router is pure host code: lint-enforced (DS-R010) on the real file
    fleet_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))),
        "deepspeed_tpu", "inference", "fleet.py",
    )
    findings = lint_paths([fleet_path])
    assert [f.rule for f in findings] == [], [f.render() for f in findings]
    # analysis green sweep over every program the fleet dispatched
    rep = run_program_passes(tel)
    t = rep["totals"]
    assert t["analysis_failures"] == 0 and t["violations"] == 0, rep
    assert t["donation_verified"] is True
    for name in rep["programs"]:
        passes = rep["programs"][name]["passes"]
        assert passes["host_transfer"]["ok"]
        assert passes["dtype_promotion"]["ok"]
        assert passes["donation"]["ok"]


def test_green_infinity_offload_program(eight_devices):
    """THE acceptance gate for streamed ZeRO-Infinity host offload
    (ISSUE 16): with pipeline_read AND pipeline_write on, the engine's
    declared stream schedule hides every H2D master/moment fetch and every
    D2H writeback behind a compute program — the overlap pass verifies the
    stream (nonzero bytes each way, ZERO exposed stream bytes) and the
    whole report stays green: no violations, donation honored on the
    per-bucket update programs, and the measured wall-clock agrees
    (exposed_ms == 0.0)."""
    import deepspeed_tpu as ds
    import deepspeed_tpu.parallel.mesh as mesh_mod
    from tests.unit.simple_model import SimpleModel, step_batch, train_steps_batch

    mesh_mod.reset_topology()
    engine, *_ = ds.initialize(
        model=SimpleModel(),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
            "zero_optimization": {
                "stage": 1,
                "offload_optimizer": {
                    "device": "cpu",
                    "pin_memory": True,
                    "pipeline_read": True,
                    "pipeline_write": True,
                    # 2 buckets on SimpleModel: real double-buffer depth
                    "bucket_size": 300,
                },
            },
            "bf16": {"enabled": True},
            "gradient_clipping": 1.0,
        },
    )
    batch = step_batch(batch_size=8, seed=0)
    train_steps_batch(engine, batch, 3)
    assert engine._streamed_offload
    rep = engine.analysis_report()
    t = rep["totals"]
    assert t["analysis_failures"] == 0 and t["violations"] == 0, rep
    assert t["donation_verified"] is True
    # the stream contract: every byte declared, every byte hidden
    assert t["stream_verified"] is True, rep
    assert t["stream_h2d_bytes"] > 0 and t["stream_d2h_bytes"] > 0
    assert t["exposed_stream_bytes"] == 0
    # and the clock agrees with the static verdict
    stats = engine.offload_stream_stats()
    assert stats["steps"] == 3 and stats["exposed_ms"] == 0.0


# ---------------------------------------------------------------------------
# jaxpr shape scan (the paged-attention structural guard's engine)
# ---------------------------------------------------------------------------
def test_find_aval_shapes_sees_through_control_flow():
    def f(x):
        def body(c, _):
            return c, jnp.broadcast_to(c, (3, 4, 4))  # materializes [3,4,4]

        _, ys = jax.lax.scan(body, x, jnp.arange(2))
        return ys

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4, 4)))
    assert find_aval_shapes(jaxpr, (3, 4, 4))
    assert not find_aval_shapes(jaxpr, (9, 9, 9))


# ---------------------------------------------------------------------------
# static HBM ledger gates (ISSUE 18)
# ---------------------------------------------------------------------------
def test_green_memory_ledger_offload(eight_devices):
    """THE memory-ledger gate for streamed ZeRO-Infinity offload: the
    static residency ledger must reproduce the shipped claim — fp32
    master + both moments live in HOST RAM while the device-side
    optimizer footprint is bounded by TWO buckets (independent of model
    size), and master/opt_state never appear as device entries. The
    ``analysis.hbm_budget_bytes`` gate is red/green testable on the same
    engine: an impossible budget raises with per-buffer attribution, and
    the observability hub surfaces the same over-budget verdict without
    raising."""
    import pytest

    import deepspeed_tpu as ds
    import deepspeed_tpu.parallel.mesh as mesh_mod
    from deepspeed_tpu.analysis import HbmBudgetError
    from tests.unit.simple_model import SimpleModel, step_batch, train_steps_batch

    mesh_mod.reset_topology()
    engine, *_ = ds.initialize(
        model=SimpleModel(),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
            "zero_optimization": {
                "stage": 3,
                "offload_optimizer": {
                    "device": "cpu",
                    "pin_memory": True,
                    "pipeline_read": True,
                    "pipeline_write": True,
                    "bucket_size": 300,  # 2 buckets on SimpleModel
                },
            },
            "bf16": {"enabled": True},
        },
    )
    batch = step_batch(batch_size=8, seed=0)
    train_steps_batch(engine, batch, 3)
    assert engine._streamed_offload
    mem = engine.memory_report()
    entries = {e["name"]: e for e in mem["entries"]}
    # master + moments are HOST resident, exactly 3x the fp32 master bytes
    host = entries["offload_host_state"]
    assert host["location"] == "host"
    master_bytes = sum(m.nbytes for m in engine._host_offload._master)
    assert host["per_chip_bytes"] == 3 * master_bytes == mem["host_bytes"]
    # device-side optimizer footprint: bounded by the 2 largest buckets
    buckets = entries["offload_device_buckets"]
    assert buckets["location"] == "device"
    srep = engine._host_offload.memory_report()
    assert srep["buckets"] == 2
    assert buckets["per_chip_bytes"] == srep["device_residency_bound_bytes"]
    assert buckets["per_chip_bytes"] <= 2 * srep["max_bucket_bytes"]
    # the model-sized master/opt trees must NOT be device entries
    device_names = {e["name"] for e in mem["entries"] if e["location"] == "device"}
    assert "master" not in device_names and "opt_state" not in device_names
    assert "params" in device_names
    assert mem["hbm_budget_verified"] is None  # no budget configured
    # red: an impossible budget raises with per-buffer attribution
    engine._config.analysis_config.hbm_budget_bytes = 1
    with pytest.raises(HbmBudgetError) as ei:
        engine.memory_report()
    assert "params" in str(ei.value) and "bytes/chip" in str(ei.value)
    # the observability hub reads the SAME over-budget verdict, no raise
    obs = engine.observability(analysis=False)
    assert obs["memory"]["hbm_budget_verified"] is False
    # green: a budget above the ledger peak verifies
    engine._config.analysis_config.hbm_budget_bytes = (
        mem["peak_hbm_bytes_per_chip"] + 1
    )
    assert engine.memory_report()["hbm_budget_verified"] is True


def test_green_memory_ledger_tp_serving():
    """THE memory-ledger gate for tp=4 sharded serving: per-chip KV bytes
    are EXACTLY total/tp with the page tables host-side, and the memory
    pass run with the TP context's declared comm schedule + sharding
    rules finds zero undeclared resharding collectives and zero
    replicated-leaf violations across every compiled serving program.
    Red twin: an empty declared schedule flags the quantized exchanges as
    undeclared."""
    from deepspeed_tpu.analysis import run_program_passes
    from deepspeed_tpu.inference.scheduler import PagedServer
    from deepspeed_tpu.inference.tp import TPServing, serving_mesh
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=4, max_seq_len=64, norm="rmsnorm", position="rope",
        activation="swiglu", use_bias=False, tie_embeddings=False,
        flash_attention=False, dtype="float32",
    )
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    G = 4
    tel = CompileTelemetry()
    tp = TPServing(mesh=serving_mesh(G), quantized_allreduce=True)
    server = PagedServer(
        cfg, params, page_size=8, max_slots=4, prefill_chunk=8,
        attn_impl="xla", dtype=jnp.float32, telemetry=tel, tp=tp,
    )
    rs = np.random.RandomState(0)
    for lens in ([5, 7], [19, 4]):
        server.serve(
            [rs.randint(0, 128, (int(n),)).astype(np.int32) for n in lens],
            max_new_tokens=6,
        )
    # the ledger claim: KV bytes/chip == total/tp, page tables host-side
    prep = server.pool.memory_report()
    assert prep["kv_devices"] == G
    assert prep["kv_bytes_per_chip"] * G == prep["kv_total_bytes"]
    assert prep["page_table_location"] == "host"
    assert prep["host_table_bytes"] > 0
    # green: the declared schedule + sharding rules verify every program
    rep = run_program_passes(
        tel,
        passes=["memory"],
        config={
            "declared_collectives": tp.declared_collectives(),
            "sharding_rules": tp.sharding_rules(),
        },
    )
    t = rep["totals"]
    assert t["analysis_failures"] == 0 and t["violations"] == 0, rep
    assert t["memory_verified"] is True
    assert t["undeclared_collectives"] == 0
    assert t["peak_hbm_bytes_per_chip"] > 0
    # red twin: the same programs against an EMPTY declared schedule —
    # every quantized exchange is now an undeclared reshard finding
    rep_red = run_program_passes(
        tel, passes=["memory"], config={"declared_collectives": []}
    )
    assert rep_red["totals"]["undeclared_collectives"] > 0
    assert rep_red["totals"]["memory_verified"] is False


def test_green_moe_programs(eight_devices):
    """THE acceptance gate for the expert-parallel MoE fast path (ISSUE 20).

    Training (ZeRO-3 + overlap_comm on a data×expert mesh): ONE compiled
    step program dispatching once per optimizer step, the full state tuple
    donated (zero double-buffered bytes), and EVERY dispatch/combine
    all-to-all hidden behind independent compute — ``overlap_verified``
    with an empty ``loop_exposed`` (exposed loop-collective bytes == 0).
    The int8-wire arm (``moe_quantized_a2a``) moves exactly fp/4 bytes on
    the wire: ``ops["all-to-all"]["quantized"]`` prices the EQuARX-style
    payloads against their fp32 equivalent, exact because fp32-vs-int8 is
    a pure dtype ratio.

    Serving: the SAME shifting-mix ragged serve as the dense gate, on an
    MoE model (top-2 + PR-MoE residual) — routing runs INSIDE the two
    paged programs (eval-mode gate, static capacity), so the compiled
    budget stays ≤ 2 ``paged_*`` programs, one dispatch per scheduler
    step, zero retraces as the expert-routing mix shifts."""
    import deepspeed_tpu as ds
    import deepspeed_tpu.parallel.mesh as mesh_mod
    from deepspeed_tpu.inference.scheduler import (
        PagedServer,
        compiled_serving_programs,
    )
    from deepspeed_tpu.models.moe_transformer import (
        MoETransformerConfig,
        MoETransformerLM,
    )

    # ---- training: 1 dispatch/step, donation green, every a2a hidden ----
    def train_a2a_summary(quantized):
        mesh_mod.reset_topology()
        # remat=False, flash_attention=False: the repo's CPU multi-device
        # convention (see tests/unit/runtime/zero/test_overlap.py) — the
        # interpret-mode flash loop and the remat transpose carry re-gather
        # sharded values per-iteration on this backend, which has nothing
        # to do with the MoE a2a schedule under test
        # use_residual (PR-MoE): the dense residual branch is the layer's
        # own independent compute — the dispatch a2a is emitted before it
        # and the combine before the next layer's gating, so the overlap
        # pass finds real work to hide the exchanges behind. fp32 keeps
        # the int8-vs-fp wire ratio an exact dtype ratio (= 4).
        cfg = MoETransformerConfig(
            vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=32, norm="rmsnorm", position="rope",
            activation="swiglu", use_bias=False, tie_embeddings=True,
            num_experts=4, moe_top_k=1, scan_layers=True, use_residual=True,
            dtype="float32",
            flash_attention=False, remat=False, moe_quantized_a2a=quantized,
        )
        engine, *_ = ds.initialize(
            model=MoETransformerLM(cfg),
            config={
                "train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3, "overlap_comm": True},
                "mesh": {"data": 4, "expert": 2},
                "steps_per_print": 10_000,
            },
        )
        rs = np.random.RandomState(0)
        toks = rs.randint(0, cfg.vocab_size, (8, 33)).astype(np.int32)
        batch = {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}
        steps = 3
        for _ in range(steps):
            engine.train_batch(batch=batch)
        step_rec = engine.compile_stats()["fused_step"]
        assert step_rec["compiles"] == 1, step_rec
        assert step_rec["dispatches"] == steps, step_rec
        rep = engine.analysis_report()
        t = rep["totals"]
        assert t["analysis_failures"] == 0 and t["violations"] == 0, rep
        assert t["donation_verified"] is True
        passes = rep["programs"]["fused_step"]["passes"]
        don = passes["donation"]["summary"]
        assert don["unhonored"] == 0 and don["double_buffered_bytes"] == 0, don
        ov = passes["overlap"]["summary"]
        assert ov["overlap_verified"] is True, ov
        assert ov["loop_exposed"] == [], ov
        assert ov["loop_collectives"] > 0, ov  # the scan body has comms
        coll = passes["collectives"]["summary"]
        a2a = coll["ops"].get("all-to-all")
        assert a2a is not None and a2a["count"] > 0, sorted(coll["ops"])
        return a2a

    fp_a2a = train_a2a_summary(quantized=False)
    q_a2a = train_a2a_summary(quantized=True)
    assert fp_a2a["quantized"]["count"] == 0, fp_a2a
    q = q_a2a["quantized"]
    # the scanned layer body appears once in the static schedule: fwd
    # dispatch + fwd combine + their two transposes = 4 int8 exchanges
    assert q["count"] == 4, q_a2a
    assert q["wire_bytes"] > 0, q_a2a
    # THE wire gate: int8 a2a bytes == fp equivalent / 4, exactly
    assert q["fp_equiv_wire_bytes"] == 4 * q["wire_bytes"], q

    # ---- serving: routing inside the ragged paged programs --------------
    mesh_mod.reset_topology()
    scfg = MoETransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=64, norm="rmsnorm", position="rope",
        activation="swiglu", use_bias=False, tie_embeddings=False,
        flash_attention=False, dtype="float32",
        num_experts=4, moe_top_k=2, use_residual=True,
    )
    model = MoETransformerLM(scfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, scfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    assert "moe" in params["layers"]  # routing params ride the layer scan
    tel = CompileTelemetry()
    server = PagedServer(
        scfg, params, page_size=8, max_slots=4, prefill_chunk=8,
        attn_impl="xla", dtype=jnp.float32, telemetry=tel,
    )
    rs = np.random.RandomState(0)
    waves = [
        [rs.randint(0, 128, (int(n),)).astype(np.int32) for n in lens]
        for lens in ([5, 7], [19, 4, 22, 9], [13])
    ]
    compiles_after = []
    for wave in waves:
        server.serve(wave, max_new_tokens=6)
        compiles_after.append(sum(r["compiles"] for r in tel.stats().values()))
    stats = tel.stats()
    assert all(n.startswith("paged_ragged_") for n in stats), stats.keys()
    assert compiled_serving_programs(stats) <= 2, stats
    # zero retraces over the shifting expert-routing mix: capacity is a
    # Python int from the static row budget, routing is pure data
    assert compiles_after[1] == compiles_after[0] == compiles_after[2], compiles_after
    for name, rec in stats.items():
        assert rec["compiles"] <= 1, f"{name} recompiled: {rec}"
    # one dispatch per scheduler step
    assert sum(r["dispatches"] for r in stats.values()) == server.stats["ragged_steps"]
    rep = run_program_passes(tel)
    t = rep["totals"]
    assert t["analysis_failures"] == 0 and t["violations"] == 0, rep
    assert t["donation_verified"] is True
    for name in rep["programs"]:
        passes = rep["programs"][name]["passes"]
        assert passes["host_transfer"]["ok"], name
        assert passes["dtype_promotion"]["ok"], name
        assert passes["donation"]["ok"], name
