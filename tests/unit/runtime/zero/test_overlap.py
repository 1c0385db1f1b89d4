"""Comm/compute overlap for ZeRO training (``runtime/zero/overlap.py``).

The contract under test (ISSUE 5): the pipelined parameter gather and the
in-scan gradient reduction are SCHEDULE transforms — they
move where collectives are issued, never what is computed. So:

* parity is ``assert_array_equal`` (bit-identity), not allclose —
  pipelined (``prefetch_layers >= 1``) vs the explicit use-point gather
  (``prefetch_layers: 0``), across ZeRO-1/3 × gas ∈ {1, 2} × fp32/bf16;
* the PR-1 invariants survive the restructuring: one fused dispatch per
  optimizer step, full state donation (checked via the analysis passes);
* the ``overlap`` analysis pass finds the compiled ZeRO-3 step's
  collectives, hides every loop-body parameter gather behind real compute
  (nonzero hidden bytes), refuses to verify the unpipelined raw-scan
  program, and fails a deliberately serialized schedule (red fixture:
  every dot depends on the loop's gather). The in-loop gradient
  reduction is one combined all-reduce on the CPU, which nothing can
  hide: ``overlap_verified`` is not evidence on this backend.

Runs comm-free on the 8-device virtual CPU mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
import deepspeed_tpu.parallel.mesh as mesh_mod
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import llama_config

VOCAB = 64
SEQ = 16
STEPS = 2


def _model(num_layers=3, remat=False):
    # einsum attention: flash-attention's CPU interpret-mode Pallas loops
    # contain genuinely-exposed slice gathers the pipeline does not own
    # (pre-existing; the overlap pass would flag them) — the overlap
    # contract is exercised on the XLA attention path
    cfg = llama_config(
        "tiny",
        hidden_size=128,
        num_heads=4,
        num_layers=num_layers,
        max_seq_len=SEQ,
        vocab_size=VOCAB,
        remat=remat,
        attn_dropout=0.0,
        hidden_dropout=0.0,
        flash_attention=False,
        scan_layers=True,
        dtype="float32",
    )
    return TransformerLM(cfg)


def _engine(zover=None, gas=1, precision="fp32", fuse=False, num_layers=3,
            remat=False, extra_cfg=None):
    mesh_mod.reset_topology()
    zero = {
        "stage": 3,
        "overlap_comm": True,
        # hidden-128 leaves all sit under the default persistence threshold
        # (1e5) — zero it so the stack is actually ZeRO-sharded and the
        # pipeline has gathers to own
        "stage3_param_persistence_threshold": 0,
        "reduce_scatter": True,
    }
    zero.update(zover or {})
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
        "zero_optimization": zero,
        "steps_per_print": 10_000,
    }
    if fuse:
        config["compile"] = {"fuse_grad_accum": True}
    if precision == "bf16":
        config["bf16"] = {"enabled": True}
    config.update(extra_cfg or {})
    engine, *_ = ds.initialize(model=_model(num_layers, remat=remat), config=config)
    return engine


def _batches(gas, steps, seed=0):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(steps):
        micro = []
        for _ in range(gas):
            toks = rs.randint(0, VOCAB, (8, SEQ + 1)).astype(np.int32)
            micro.append({"input_ids": toks[:, :-1], "labels": toks[:, 1:]})
        out.append(micro)
    return out


def _train(engine, batches):
    return [
        np.asarray(jax.device_get(engine.train_batch(iter(list(micro)))))
        for micro in batches
    ]


def _plan(engine):
    """The overlap plan is built with the jitted programs on the first
    batch (init_params is lazy) — trigger it with one forward."""
    engine(_batches(1, 1)[0][0])
    return engine._overlap_plan


def _masters(engine):
    flat, _ = jax.tree_util.tree_flatten_with_path(engine.get_master_params())
    return [(jax.tree_util.keystr(p), np.asarray(jax.device_get(l))) for p, l in flat]


def _assert_states_identical(ea, eb):
    for (ka, va), (kb, vb) in zip(_masters(ea), _masters(eb)):
        assert ka == kb
        np.testing.assert_array_equal(va, vb, err_msg=f"master leaf {ka} diverged")


def _schedule(engine, program):
    from deepspeed_tpu.analysis.hlo import collective_schedule

    return collective_schedule(engine._telemetry.compiled_text(program))


def _assert_same_sum_in_another_order(ref, lref, e, l):
    """Two engines that add the same per-chip partial gradients in a
    different ORDER (since PR 62 the in-loop reduction adds a matrix's
    itself, in float32, as its sends arrive: ``OverlapPlan._wgrad_by_sends``;
    the partitioner's all-reduce adds them in its own). The first step's
    loss is the same to the bit (no gradient is in it); the later ones agree
    to float32's last place (4.243444 against 4.243445). Adam divides a
    gradient by its own size, so where a gradient is all rounding the last
    bit moves a whole update's worth (lr 1e-2): all but a thousandth of a
    leaf's elements agree to 5e-6, and none is further off than a hundredth
    of one update (seen: 10 of 98,304 elements, at most 3.2e-5)."""
    np.testing.assert_array_equal(lref[0], l[0])
    np.testing.assert_allclose(lref[1:], l[1:], rtol=1e-6)
    for (ka, va), (kb, vb) in zip(_masters(ref), _masters(e)):
        assert ka == kb
        gap = np.abs(va - vb)
        assert gap.max() <= 1e-4 and np.mean(gap > 5e-6) <= 1e-3, (ka, gap.max(), np.mean(gap > 5e-6))


# ---------------------------------------------------------------------------
# parity: pipelined vs use-point gather is bit-identical
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("stage", [1, 3])
@pytest.mark.parametrize("gas", [1, 2])
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_overlap_parity_bit_identical(stage, gas, precision, eight_devices):
    """Losses AND the full master tree match exactly (=, not allclose)
    between the pipelined step and the unpipelined (depth-0) step."""
    batches = _batches(gas, STEPS)
    e0 = _engine({"stage": stage, "prefetch_layers": 0}, gas, precision)
    l0 = _train(e0, batches)
    e1 = _engine({"stage": stage, "prefetch_layers": 1}, gas, precision)
    l1 = _train(e1, batches)
    if stage >= 3:
        # guard against vacuous parity: the pipeline must actually engage
        assert e1._overlap_plan is not None and e1._overlap_plan.prefetch_enabled
        assert e1._overlap_plan.depth == 1
        assert e0._overlap_plan is not None and e0._overlap_plan.depth == 0
    else:
        # stage 1 has nothing to prefetch or scatter: the knob must no-op
        assert e1._overlap_plan is None and e0._overlap_plan is None
    for a, b in zip(l0, l1):
        np.testing.assert_array_equal(a, b)
    _assert_states_identical(e0, e1)


def test_depth2_and_reduce_off_still_bit_identical(eight_devices):
    """Pipeline depth is schedule-only at every depth: bit-identical. The
    in-loop reduction computes the same sum, in another order
    (``_assert_same_sum_in_another_order``)."""
    batches = _batches(1, STEPS)
    ref = _engine({"prefetch_layers": 0})
    lref = _train(ref, batches)
    e = _engine({"prefetch_layers": 2})
    for a, b in zip(lref, _train(e, batches)):
        np.testing.assert_array_equal(a, b)
    _assert_states_identical(ref, e)

    e = _engine({"prefetch_layers": 1, "reduce_scatter": False})
    assert not e._overlap_plan.reduce_enabled if e._overlap_plan is not None else True
    _assert_same_sum_in_another_order(ref, lref, e, _train(e, batches))


def test_remat_parity_bit_identical(eight_devices):
    """cfg.remat wraps the pipelined scan body (fresh custom_vjp closures +
    jax.linear_transpose inside jax.checkpoint) — the combination most
    prone to remat/transpose interaction regressions across jax versions.
    The bit-exact contract must hold there too."""
    batches = _batches(1, STEPS)
    e0 = _engine({"prefetch_layers": 0}, remat=True)
    l0 = _train(e0, batches)
    e1 = _engine({"prefetch_layers": 1}, remat=True)
    l1 = _train(e1, batches)
    assert e1._overlap_plan is not None and e1._overlap_plan.prefetch_enabled
    for a, b in zip(l0, l1):
        np.testing.assert_array_equal(a, b)
    _assert_states_identical(e0, e1)


def test_pld_disables_prefetch_visibly(eight_devices):
    """PLD owns the layer loop (cond-skipped layers) — the prefetch
    pipeline does not run there. The plan must SAY so (prefetch_enabled
    False) instead of reporting a pipeline that never engaged; the in-scan
    grad reduction still applies."""
    plan = _plan(_engine(
        {"prefetch_layers": 1},
        extra_cfg={"progressive_layer_drop": {
            "enabled": True, "theta": 0.5, "gamma": 0.001}},
    ))
    assert plan is not None
    assert not plan.prefetch_enabled and plan.depth == 0
    assert plan.reduce_enabled


def test_explicit_gather_matches_raw_scan_allclose(eight_devices):
    """The raw scan (no plan: GSPMD places the gathers itself) reassociates
    the distributed grad sum at the last ulp, so raw-vs-explicit is a tight
    allclose, not = (the bit-exact contract binds the plan's depths to each
    other, not to GSPMD's free choice)."""
    batches = _batches(1, STEPS)
    e0 = _engine({"prefetch_layers": 0})
    l0 = _train(e0, batches)
    eraw = _engine({"overlap_comm": False, "prefetch_layers": None})
    assert eraw._overlap_plan is None
    lraw = _train(eraw, batches)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(lraw), rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the in-loop reduction: every leaf alone, where it lies
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_in_loop_reduction_parity_bit_identical_at_every_depth(precision, remat, eight_devices):
    """With every leaf reduced alone, where it lies, depth 1 and depth 2
    still equal depth 0 bit for bit, with and without remat: every depth
    takes the same reduction, only the gather's place moves."""
    batches = _batches(1, STEPS)
    e0 = _engine({"prefetch_layers": 0}, precision=precision, remat=remat)
    l0 = _train(e0, batches)
    record = e0._overlap_plan.reduction_record()
    assert record["leaves_alone"] >= 6 and record["leaves_left_to_partitioner"] == 0, record
    for depth in (1, 2):
        e = _engine({"prefetch_layers": depth}, precision=precision, remat=remat)
        l = _train(e, batches)
        assert e._overlap_plan.depth == depth and e._overlap_plan.reduction_record() == record
        for a, b in zip(l0, l):
            np.testing.assert_array_equal(a, b)
        _assert_states_identical(e0, e)


@pytest.mark.parametrize("stage", [2, 3])
def test_in_loop_reduction_matches_reduce_off(stage, eight_devices):
    """Where a leaf's sum is made (in the loop, a matrix's by the plan's own
    sends; or not at all in the loop: ``reduce_scatter: False``) moves no
    value but by the order of the additions: the same partials are summed.
    Stage 2 takes the plain scan body (nothing to gather), stage 3 the
    pipelined one."""
    batches = _batches(1, STEPS)
    on = _engine({"stage": stage, "prefetch_layers": 1})
    lon = _train(on, batches)
    assert on._overlap_plan.reduce_enabled and on._overlap_plan.prefetch_enabled == (stage == 3)
    off = _engine({"stage": stage, "prefetch_layers": 1, "reduce_scatter": False})
    loff = _train(off, batches)
    assert off._overlap_plan is None or not off._overlap_plan.reduce_enabled
    _assert_same_sum_in_another_order(on, lon, off, loff)


_XL_LAYER = {  # GPT-2 XL's sixteen leaves a layer, in tree order: H 1,600, I 6,400
    "attn_norm_bias": (1600,), "attn_norm_scale": (1600,), "b_in": (6400,), "b_out": (1600,),
    "bk": (1600,), "bo": (1600,), "bq": (1600,), "bv": (1600,),
    "mlp_norm_bias": (1600,), "mlp_norm_scale": (1600,),
    "w_in": (1600, 6400), "w_out": (6400, 1600),
    "wk": (1600, 1600), "wo": (1600, 1600), "wq": (1600, 1600), "wv": (1600, 1600),
}


def _stack_plan(shapes, param_specs, mesh_cfg, layers=2):
    """A stage-3 plan for a stack of ``shapes`` a layer whose leaves are cut
    as ``param_specs`` says (the leading entry is the layer dim's)."""
    from deepspeed_tpu.parallel.mesh import MeshConfig, initialize_topology
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
    from deepspeed_tpu.runtime.zero.overlap import build_overlap_plan

    mesh_mod.reset_topology()
    topo = initialize_topology(MeshConfig(**mesh_cfg))
    stacked = {k: jax.ShapeDtypeStruct((layers,) + s, jnp.bfloat16) for k, s in shapes.items()}
    zero = DeepSpeedZeroConfig(stage=3, overlap_comm=True)
    return build_overlap_plan(zero, topo, stacked, param_specs, param_specs, layers)


def test_reduction_record_of_gpt2_xl_layer(eight_devices):
    """GPT-2 XL's sixteen leaves a layer are all cut by ZeRO and nothing
    else, so the plan reduces all sixteen alone, in their own shapes: the
    six matrices (2.56 M and 10.24 M elements) and the ten vectors."""
    plan = _stack_plan(_XL_LAYER, {k: P(None, "data") for k in _XL_LAYER}, {"data": 8})
    assert plan.reduce_enabled and all(info.zero_only for info in plan.leaves)
    record = plan.reduction_record()
    assert sorted(record) == ["alone_elems", "leaves_alone", "leaves_left_to_partitioner"]
    assert (record["leaves_alone"], record["leaves_left_to_partitioner"]) == (16, 0)
    assert record["alone_elems"] == 2 * [1600] + [6400] + 7 * [1600] + 2 * [10_240_000] + 4 * [2_560_000]


def test_tp_mixed_leaf_is_left_to_the_partitioner(eight_devices):
    """A leaf with a second real sharding (a TP-cut dim beside ZeRO's) is
    not the plan's to reduce: its gathered layout is still sharded, so the
    constraint would not be its whole reduction. A size-1 ``model`` axis
    shards nothing and does not count."""
    shapes = {"norm": (128,), "w_col": (128, 256), "w_row": (256, 128)}
    specs = {"norm": P(None, "data"), "w_col": P(None, "data", "model"), "w_row": P(None, "model", "data")}
    plan = _stack_plan(shapes, specs, {"data": 4, "model": 2})
    assert [info.zero_only for info in plan.leaves] == [True, False, False]
    assert plan.reduction_record() == {"leaves_alone": 1, "leaves_left_to_partitioner": 2, "alone_elems": [128]}
    plan = _stack_plan(shapes, specs, {"data": 8, "model": 1})
    assert plan.reduction_record()["leaves_alone"] == 3


@pytest.mark.parametrize("mesh_cfg, w_col", [({"data": 8}, None), ({"data": 4, "model": 2}, "model")])
def test_reduce_grads_backward_is_one_constraint_a_leaf(mesh_cfg, w_col, eight_devices):
    """What the backward of ``reduce_grads`` holds: one sharding constraint
    for every ``zero_only`` leaf, on the leaf in its own shape, and nothing
    else: no transpose, reshape, pad or concatenate (the ``[world, chunk]``
    bucket, until PR 58). A TP-mixed leaf gets none."""
    shapes = {"norm": (128,), "w_col": (128, 256), "w_row": (256, 128)}
    specs = {"norm": P(None, "data"), "w_col": P(None, "data", w_col), "w_row": P(None, None, "data")}
    plan = _stack_plan(shapes, specs, mesh_cfg)
    alone = [k for k, info in zip(sorted(shapes), plan.leaves) if info.zero_only]
    assert alone == [k for k in sorted(shapes) if not (k == "w_col" and w_col)]

    def loss(tree):
        return sum(jnp.sum(v.astype(jnp.float32) ** 2) for v in plan.reduce_grads(tree).values())

    with plan.mesh:
        jaxpr = jax.make_jaxpr(jax.grad(loss))({k: jnp.ones(s, jnp.bfloat16) for k, s in shapes.items()})
    eqns = jaxpr.jaxpr.eqns
    names = [e.primitive.name for e in eqns]
    constrained = sorted(e.invars[0].aval.shape for e in eqns if e.primitive.name == "sharding_constraint")
    assert constrained == sorted(shapes[k] for k in alone), names
    assert not {"transpose", "reshape", "pad", "concatenate", "custom_vjp_call"} & set(names), names


def test_engine_emits_the_reduction_record_once(eight_devices):
    """The engine says which leaves the plan reduces, once, where it builds
    it: an instant event of its tracer carrying the record."""
    e = _engine({"prefetch_layers": 1})
    _train(e, _batches(1, STEPS))
    events = [s for s in e.tracer.spans() if s["name"] == "zero.grad_reduce_plan"]
    assert len(events) == 1 and events[0]["ph"] == "i"
    assert events[0]["attrs"] == e._overlap_plan.reduction_record()
    assert events[0]["attrs"]["leaves_alone"] >= 6 and events[0]["attrs"]["leaves_left_to_partitioner"] == 0


def test_engine_says_how_the_loops_collectives_were_scheduled_once(eight_devices):
    """After the step's first compile the engine reads the compiled text
    (no second compile: the dispatch's own executable) and says, once, how
    many collectives the loops hold and how many are left on the core. The
    CPU mesh has no schedule: every one reads synchronous here."""
    e = _engine({"prefetch_layers": 1})
    _train(e, _batches(1, STEPS))
    events = [s for s in e.tracer.spans() if s["name"] == "zero.collective_schedule"]
    assert len(events) == 1 and events[0]["ph"] == "i"
    said = events[0]["attrs"]
    assert set(said) == {"program", "loop_collectives", "async_with_compute_between", "sync_on_core", "sync_bytes"}
    assert said["program"] == "fused_step" and said["loop_collectives"] == said["sync_on_core"] > 0
    assert said["async_with_compute_between"] == 0 and said["sync_bytes"] > 0
    in_loop = [r for r in _schedule(e, "fused_step") if r["in_loop"]]
    assert said["loop_collectives"] == len(in_loop) and said["sync_bytes"] == sum(r["bytes"] for r in in_loop)
    assert e.compile_stats()["fused_step"]["compiles"] == 1
    plain = _engine({"overlap_comm": False})  # no plan, nothing said
    _train(plain, _batches(1, 1))
    assert plain._overlap_plan is None and not [s for s in plain.tracer.spans() if s["name"] == "zero.collective_schedule"]


# ---------------------------------------------------------------------------
# where the stacked gradient leaves the backward scan
# ---------------------------------------------------------------------------
def _scan_eqns(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _scan_eqns(sub, out)
    return out


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("prefetch_layers", [0, 1, 2])
def test_backward_scan_writes_each_layer_grad_as_ys(prefetch_layers, remat, eight_devices):
    """The stack is scanned (``xs``) for the layer's use, so its cotangent
    leaves the backward scan as ``ys`` — one slice written in place a
    layer. A stack the body closes over gets a carry-held accumulator of its
    whole shape instead, ``acc += update_slice(zeros, g, i)`` every
    iteration: a pass over all L layers to add one (the ``select_add``
    fusions over ``[48,1600,1600]`` that headed GPT-2 XL's step). Read from
    the jaxpr: no compiler in the loop."""
    e = _engine({"prefetch_layers": prefetch_layers}, num_layers=6, remat=remat)
    batch = jax.tree_util.tree_map(jnp.asarray, _batches(1, 1)[0][0])
    e.init_params(batch)
    assert e._overlap_plan.prefetch_enabled and e._overlap_plan.depth == prefetch_layers
    stacked = sorted(tuple(l.shape) for l in jax.tree_util.tree_leaves(e._params["layers"]))
    jaxpr = jax.make_jaxpr(jax.grad(e._loss_of))(e._params, batch, jax.random.PRNGKey(0))
    (bwd,) = [s for s in _scan_eqns(jaxpr.jaxpr, []) if s.params["reverse"]]
    n_carry = bwd.params["num_carry"]
    carries = [tuple(v.aval.shape) for v in bwd.outvars[:n_carry]]
    ys = [tuple(v.aval.shape) for v in bwd.outvars[n_carry:]]
    assert not [c for c in carries if c in stacked], carries
    assert sorted(y for y in ys if y in stacked) == stacked, ys


# ---------------------------------------------------------------------------
# plan gating and the in-flight byte budget
# ---------------------------------------------------------------------------
def test_plan_gating(eight_devices):
    # default persistence threshold: every hidden-128 leaf is persistent
    # (replicated), so there is nothing to prefetch — but the bucketed
    # reduce transform still applies
    plan = _plan(_engine({"stage3_param_persistence_threshold": 100_000}))
    assert plan is not None
    assert not plan.prefetch_enabled
    assert plan.reduce_enabled
    # ZeRO++ quantized wire formats own their gather/reduce schedules
    assert _plan(_engine({"zero_quantized_weights": True})) is None


def test_prefetch_bucket_size_caps_depth(eight_devices):
    """stage3_prefetch_bucket_size bounds in-flight prefetched elements:
    a 1-element budget forces the pipeline down to depth 1 (never 0 — one
    layer of lookahead is the floor while prefetch is on)."""
    assert _plan(_engine({"prefetch_layers": 2, "stage3_prefetch_bucket_size": int(5e7)})).depth == 2
    assert _plan(_engine({"prefetch_layers": 2, "stage3_prefetch_bucket_size": 1})).depth == 1


# ---------------------------------------------------------------------------
# PR-1 invariants survive the pipeline
# ---------------------------------------------------------------------------
def test_one_dispatch_and_donation_preserved(eight_devices):
    """Pipelined + bucketed + fused grad-accum still runs ONE jitted program
    per optimizer step, compiles once, and donates-and-aliases the full
    state (via the analysis passes, like PR 3 moved the old runtime
    probes)."""
    e = _engine({"prefetch_layers": 1}, gas=2, precision="bf16", fuse=True)
    _train(e, _batches(2, 3))
    stats = e.compile_stats()
    fused = stats["fused_accum_step"]
    assert fused["dispatches"] == 3, stats
    assert fused["compiles"] == 1, stats
    assert stats["fwd_bwd"]["dispatches"] == 0, stats
    assert stats["step"]["dispatches"] == 0, stats
    rep = e.analysis_report(programs=["fused_accum_step"])
    entry = rep["programs"]["fused_accum_step"]["passes"]
    assert entry["donation"]["ok"], entry["donation"]["violations"]
    assert entry["donation"]["summary"].get("double_buffered_bytes", 0) == 0
    assert entry["host_transfer"]["ok"], entry["host_transfer"]["violations"]


# ---------------------------------------------------------------------------
# the overlap analysis pass: green on the real program, red on serialized
# ---------------------------------------------------------------------------
def test_overlap_pass_green_on_pipelined_zero3_step(eight_devices):
    """What a CPU compile of the ZeRO-3 pipelined step establishes, and what
    it cannot. The passes read the compiled text's collectives (their count
    and bytes by kind), every parameter gather inside a loop body (since PR
    62 the sends of the lookahead, ``world - 1`` a cut leaf) has
    independent real compute to hide behind (the prefetch: the raw,
    plan-less scan of the same model and mesh exposes three), hidden bytes
    are nonzero, and a step is one dispatch. The in-loop gradient reduction
    IS in the backward loop's body, but XLA's CPU pipeline combines PR 58's
    per-leaf reductions into ONE variadic all-reduce over a layer's nine
    gradient dots, so no dot of that body is independent of it (until PR 62
    took the matrices out of it: their gradients are summed by sends, and
    what is left is the vectors'): the CPU schedule offers nothing to verify
    there, and ``overlap_verified`` is not evidence on this backend (the
    ledger has the measured collective share of the chip's schedule)."""
    e = _engine({"prefetch_layers": 1})
    _train(e, _batches(1, 1))
    rep = e.analysis_report(passes=["overlap", "collectives"])
    t, ov = rep["totals"], rep["programs"]["fused_step"]["passes"]["overlap"]["summary"]
    assert t["analysis_failures"] == 0 and t["collective_count"] == ov["collectives"] >= 20, t
    # since PR 62 the lookahead gathers a cut leaf by direct sends (collective-permutes): the all-gathers left are the
    # once-a-step ones of the embedding and the head
    assert set(t["collectives"]) == {"all-gather", "all-reduce", "all-to-all", "collective-permute"}, t
    assert t["collective_bytes"] > 0, t
    assert t["hidden_collective_bytes"] == ov["hidden_bytes"] > 0 and ov["loop_collectives"] >= 4, ov
    layers = jax.tree_util.tree_leaves(e.get_master_params()["layers"])
    layer_bytes = sum(leaf.size // leaf.shape[0] for leaf in layers) * 4
    # no collective of the loops is exposed: since PR 62 a matrix's gradient is summed by the plan's sends, and the
    # vectors' one combined all-reduce (a layer's bytes less its matrices') has the matrices' gradient dots beside it
    assert ov["loop_exposed"] == [], ov
    matrices = sum(leaf.size // leaf.shape[0] for leaf in layers if leaf.ndim == 3) * 4
    assert any(r["op"] == "all-reduce" and r["bytes"] == layer_bytes - matrices and r["in_loop"]
               for r in _schedule(e, "fused_step")), layer_bytes - matrices
    assert e.compile_stats()["fused_step"]["dispatches"] == 1

    eraw = _engine({"overlap_comm": False})
    assert eraw._overlap_plan is None
    _train(eraw, _batches(1, 1))
    traw = eraw.analysis_report(passes=["overlap"])
    raw = traw["programs"]["fused_step"]["passes"]["overlap"]["summary"]
    assert traw["totals"]["overlap_verified"] is False, traw["totals"]
    assert [x for x in raw["loop_exposed"] if x["op"] == "all-gather"], raw  # the use-point gathers the pipeline takes out of the way


def test_overlap_pass_red_serialized_schedule(eight_devices):
    """Red fixture: a scan whose every dot depends on the loop-body param
    gather — the serialized schedule the pipeline exists to prevent. The
    pass must refuse to verify it and name the exposed collective."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.analysis import analyze_program
    from deepspeed_tpu.profiling.compile_telemetry import CompileTelemetry
    from jax import shard_map

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("x",))
    # a stacked, ZeRO-sharded layer stack: the per-iteration slice makes the
    # gather loop-VARIANT, so licm cannot hoist it out of the while body
    # (a loop-invariant gather gets hoisted and stops being a loop finding)
    ws = jax.device_put(
        jnp.stack([jnp.eye(64, dtype=jnp.float32)] * 4),
        NamedSharding(mesh, P(None, "x", None)),
    )
    x = jax.device_put(
        jnp.ones((64, 64), jnp.float32), NamedSharding(mesh, P(None, None))
    )

    def gather(t):
        return shard_map(
            lambda s: jax.lax.all_gather(s, "x", tiled=True),
            mesh=mesh,
            in_specs=P("x", None),
            out_specs=P(None, None),
            check_vma=False,
        )(t)

    def serialized(x, ws):
        def body(c, i):
            w = jax.lax.dynamic_index_in_dim(ws, i, axis=0, keepdims=False)
            g = gather(w)  # use-point gather: the compute below depends on it
            return c @ g, None

        out, _ = jax.lax.scan(body, x, jnp.arange(4, dtype=jnp.int32))
        return out

    tel = CompileTelemetry()
    fn = tel.instrument("serialized", serialized)
    fn(x, ws)
    res = analyze_program(
        "serialized", tel.programs()["serialized"], passes=["overlap"]
    )["overlap"]
    assert res.summary["loop_collectives"] >= 1, res.summary
    assert res.summary["overlap_verified"] is False, res.summary
    assert res.violations and res.violations[0].severity == "warn"
    # require_overlap escalates the finding to error severity (CI gate mode)
    res = analyze_program(
        "serialized",
        tel.programs()["serialized"],
        passes=["overlap"],
        config={"require_overlap": True},
    )["overlap"]
    assert res.violations and res.violations[0].severity == "error"
