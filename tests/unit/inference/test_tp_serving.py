"""Multi-chip tensor-parallel serving (ISSUE 13): CPU-mesh parity suite.

The whole test session runs on a virtual CPU mesh
(``--xla_force_host_platform_device_count=8``, tests/conftest.py), so the
sharded ragged programs here exercise the SAME ``shard_map``/GSPMD code
paths a TPU pod runs. Load-bearing checks:

* **byte-identical greedy streams** at tp ∈ {1, 2, 4} (fp32 weights, fp
  all-reduces) against the single-chip ragged oracle AND the dense
  lockstep ``decode.generate`` — across mid-stream admission, recompute
  preemption, prefix-cache attach, per-request spec-K verify rows, and
  fused multi-step windows;
* the serving invariants hold ON THE MESH: ≤ 2 compiled ``paged_*``
  programs, exactly 1 dispatch per scheduler step, no retrace across
  shifting waves (the analysis-side gate is
  ``test_passes.py::test_green_tp_serving``);
* the **int8 weight** contract: elementwise roundtrip error ≤
  ``max|w_channel| / 254`` (the documented bound), logits allclose within
  the bound's linear propagation, serving runs end-to-end;
* the **quantized all-reduce** contract: allclose to the fp ``psum``
  within the two-stage symmetric-int8 error model (NOT byte-identical —
  the knob trades exactness for 4x less wire traffic).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.compression.int8 import (
    QuantizedTensor,
    dequantize,
    qmatmul,
    quantize_params_int8,
    quantize_weight_int8,
)
from deepspeed_tpu.inference import decode
from deepspeed_tpu.inference.scheduler import PagedServer, compiled_serving_programs
from deepspeed_tpu.inference.spec_decode import Drafter
from deepspeed_tpu.inference.tp import TPServing, quantized_all_reduce, serving_mesh
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.profiling.compile_telemetry import CompileTelemetry
from jax import shard_map

# MHA config: head axes divide by 4 so the same weights serve tp ∈ {1,2,4}
CFG = dict(
    vocab_size=128,
    hidden_size=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=4,
    max_seq_len=64,
    norm="rmsnorm",
    position="rope",
    activation="swiglu",
    use_bias=False,
    tie_embeddings=False,
    flash_attention=False,
    dtype="float32",
)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(**CFG)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), toks)
    return cfg, model, params


def _prompts(n, seed=0, lo=3, hi=20):
    rs = np.random.RandomState(seed)
    return [
        rs.randint(0, CFG["vocab_size"], (int(rs.randint(lo, hi)),)).astype(np.int32)
        for _ in range(n)
    ]


def _dense(cfg, params, prompt, n, eos=None):
    return np.asarray(decode.generate(cfg, params, prompt[None], n, eos_token_id=eos))[0]


def _server(cfg, params, tp=None, **kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("max_slots", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("dtype", jnp.float32)
    return PagedServer(cfg, params, tp=tp, **kw)


def _tp(degree, **kw):
    return TPServing(mesh=serving_mesh(degree), **kw)


class MixDrafter(Drafter):
    """Row uid drafts uid % 3 tokens — rounds carry 0/1/2-draft rows at
    once, so verify resolution (global argmax + accepted prefix) runs on
    genuinely ragged spec-K rows under the sharded program."""

    def propose(self, uid, context, k):
        return np.arange(min(k, uid % 3), dtype=np.int32)


# --- byte-identical parity on the mesh --------------------------------------
@pytest.mark.parametrize("degree", [1, 2, 4])
def test_tp_matches_single_chip_mixed_serve(model_and_params, degree):
    """The acceptance core: tp ∈ {1,2,4} greedy streams byte-identical to
    the single-chip ragged oracle (and dense), with the compile/dispatch
    budget intact on the mesh — ≤ 2 paged programs, 1 dispatch/step, no
    retrace between waves."""
    cfg, _, params = model_and_params
    prompts = _prompts(6, seed=2)
    budgets = [10, 3, 7, 12, 1, 5]
    oracle = _server(cfg, params).serve(prompts, max_new_tokens=budgets)
    tel = CompileTelemetry()
    srv = _server(cfg, params, tp=_tp(degree), telemetry=tel)
    outs = srv.serve(prompts[:3], max_new_tokens=budgets[:3])
    compiles_w1 = sum(r["compiles"] for r in tel.stats().values())
    outs += srv.serve(prompts[3:], max_new_tokens=budgets[3:])  # wave 2
    for p, n, a, b in zip(prompts, budgets, outs, oracle):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, _dense(cfg, params, p, n))
    stats = tel.stats()
    assert compiled_serving_programs(stats) <= 2, stats.keys()
    assert sum(r["compiles"] for r in stats.values()) == compiles_w1, (
        "wave 2 retraced a sharded program"
    )
    assert sum(r["dispatches"] for r in stats.values()) == srv.stats["ragged_steps"]
    assert srv.serve_stats()["tp_degree"] == degree
    assert srv.pool.used_pages() == 0 and srv.pool.live_tokens() == 0


def test_tp_gqa_kv_head_shard(model_and_params):
    """GQA under the kv-head split: NKV=2 shards 1 kv head per chip at
    tp=2 while each chip keeps its 2 query heads — the group size is
    invariant and streams stay byte-identical."""
    cfg = TransformerConfig(**{**CFG, "num_kv_heads": 2})
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(3), toks)
    prompts = _prompts(4, seed=6)
    ref = _server(cfg, params).serve(prompts, max_new_tokens=8)
    got = _server(cfg, params, tp=_tp(2)).serve(prompts, max_new_tokens=8)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)


def test_tp_preemption_parity(model_and_params):
    """Recompute preemption under an undersized pool is pure host logic —
    the sharded path must preempt and resume byte-identically (page
    tables are replicated; only page contents shard)."""
    cfg, _, params = model_and_params
    kw = dict(page_size=4, num_pages=14, max_slots=3, prefill_chunk=8)
    prompts = _prompts(4, seed=4, lo=6, hi=14)
    srv = _server(cfg, params, tp=_tp(2), **kw)
    outs = srv.serve(prompts, max_new_tokens=12)
    assert srv.stats["preempted"] >= 1, "pool was sized to force preemption"
    for p, a in zip(prompts, outs):
        np.testing.assert_array_equal(a, _dense(cfg, params, p, 12))
    assert srv.pool.used_pages() == 0


def test_tp_prefix_cache_attach_parity(model_and_params):
    """Prefix attach + CoW ride the sharded pools untouched: the barrier's
    donated page copy runs on the kv-head-sharded arrays, hits register,
    and streams stay byte-identical to sharing-off serving."""
    cfg, _, params = model_and_params
    rs = np.random.RandomState(21)
    sys_tokens = rs.randint(0, 128, (19,)).astype(np.int32)
    prompts = [
        np.concatenate([sys_tokens, rs.randint(0, 128, (3 + i,)).astype(np.int32)])
        for i in range(4)
    ]
    srv = _server(cfg, params, tp=_tp(2), prefix_cache=True)
    first = srv.serve(prompts[:1], max_new_tokens=4)
    rest = srv.serve(prompts[1:], max_new_tokens=4)
    assert srv.pool.stats["prefix_hit_pages"] > 0, "prefix cache never engaged"
    oracle = _server(cfg, params, prefix_cache=False).serve(prompts, max_new_tokens=4)
    for a, b in zip(first + rest, oracle):
        np.testing.assert_array_equal(a, b)


def test_tp_spec_decode_parity(model_and_params):
    """Per-request spec-K verify rows resolve through the GLOBAL argmax on
    the mesh (vocab-sharded logits): accepted prefixes and bonus tokens
    must match spec-off single-chip serving byte-for-byte."""
    cfg, _, params = model_and_params
    prompts = _prompts(4, seed=5)
    ref = _server(cfg, params).serve(prompts, max_new_tokens=8)
    srv = _server(
        cfg, params, tp=_tp(2),
        spec_decode={"max_draft": 2}, drafter=MixDrafter(),
    )
    outs = srv.serve(prompts, max_new_tokens=8)
    assert srv.stats["spec_rounds"] >= 1, "the mix never drafted"
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", ["budgets_alone", "an_eos_a_request"])
def test_tp_streams_with_a_step_in_flight_are_a_drained_servers(model_and_params, case):
    """The sharded server runs ahead as the single-chip one does: a row's
    next token is gathered on the mesh from the unsettled result
    (``build_token_feed(tp)``: the window is placed replicated whatever fed
    it), an EOS is seen a step late and its row-step discarded, and the
    streams are those of the same server settled after every step and of the
    single-chip one."""
    cfg, _, params = model_and_params
    prompts = _prompts(5, seed=9, lo=4, hi=20)
    budgets = [12, 9, 14, 10, 11]
    ref = _server(cfg, params).serve(prompts, max_new_tokens=budgets)
    eos = [int(f[p.size + 2 + i]) if case == "an_eos_a_request" else None for i, (p, f) in enumerate(zip(prompts, ref))]
    runs = []
    for drained in (False, True):
        srv = _server(cfg, params, tp=_tp(2))
        uids = [srv.submit(p, max_new_tokens=n, eos_token_id=e) for p, n, e in zip(prompts, budgets, eos)]
        while srv.has_work():
            srv.step()
            if drained:
                srv.settle()
        runs.append((srv, [srv.take_result(u) for u in uids]))
    (ahead, got), (sync, want) = runs
    for a, b, f, p, e in zip(got, want, ref, prompts, eos):
        np.testing.assert_array_equal(a, b)
        end = f.size if e is None else p.size + int(np.flatnonzero(f[p.size:] == e)[0]) + 1
        np.testing.assert_array_equal(a, f[:end])
    assert ahead.serve_stats()["run_ahead_share"] > 0.8 and set(ahead.stats["drain_reasons"]) <= {"idle"}
    assert sync.stats["run_ahead_steps"] == 0 and sync.stats["overshoot_rows"] == 0
    assert (ahead.stats["overshoot_rows"] >= 1) == (case == "an_eos_a_request")
    for srv in (ahead, sync):
        assert srv.pool.used_pages() == 0 and srv._in_flight is None


# --- config / validation red tests ------------------------------------------
def test_tp_divisibility_and_weight_bits_validation(model_and_params):
    cfg, _, params = model_and_params
    bad = TransformerConfig(**{**CFG, "num_heads": 6, "num_kv_heads": 3})
    with pytest.raises(ValueError, match="divide"):
        _server(bad, params, tp=_tp(4))
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    with pytest.raises(Exception, match="weight_quant_bits"):
        DeepSpeedInferenceConfig(paged_kv={"sharded": {"weight_quant_bits": 4}})


@pytest.mark.parametrize(
    "sharded, engine_kw",
    [
        ({"tp_degree": 2}, {}),
        # the FOLLOW mode: no ``sharded`` block, so sharded.tp_degree stays 0
        # and defers to the engine-level degree
        (None, {"tensor_parallel": {"tp_size": 2}}),
    ],
    ids=["sharded_tp_degree", "follows_tensor_parallel"],
)
def test_tp_engine_knob_routing(model_and_params, sharded, engine_kw):
    """The degree, given either way, routes through the engine: the built
    server runs the sharded programs and reports its degree."""
    cfg, _, params = model_and_params
    paged_kv = {"page_size": 8, "max_slots": 4, "prefill_chunk": 8, "attn_impl": "xla"}
    if sharded:
        paged_kv["sharded"] = sharded
    engine = ds.init_inference(TransformerLM(cfg), dtype="fp32", paged_kv=paged_kv, **engine_kw)
    assert engine._config.paged_kv.sharded.tp_degree == (2 if sharded else 0)
    engine.set_params(params)
    engine._ds_config = cfg
    prompts = _prompts(2, seed=8)
    outs = engine.serve(prompts, max_new_tokens=4)
    assert all(o is not None for o in outs)
    assert engine._paged_server.tp.degree == 2
    assert any(n.endswith("_tp2") for n in engine.compile_stats() if n.startswith("paged_ragged_"))
    st = engine.serve_stats()
    assert st["tp_degree"] == 2 and st["finished"] == 2
    ref = _server(cfg, params).serve(prompts, max_new_tokens=4)
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)


# --- int8 weights: the documented tolerance contract ------------------------
def test_int8_weight_roundtrip_bound(model_and_params):
    """The documented bound: per-output-channel symmetric int8 means
    ``|w - dequant(quant(w))| <= max|w_channel| / 254`` elementwise, and
    the fused-epilogue matmul equals the dequantize-then-matmul form."""
    cfg, _, params = model_and_params
    w = np.asarray(params["layers"]["wq"])  # stacked [L, H, NH*D]
    qt = quantize_weight_int8(w)
    assert isinstance(qt, QuantizedTensor) and qt.q.dtype == jnp.int8
    deq = np.asarray(dequantize(qt))
    bound = np.max(np.abs(w), axis=-2, keepdims=True) / 254.0 + 1e-7
    assert np.all(np.abs(w - deq) <= bound), (
        f"roundtrip exceeded max|w_channel|/254: "
        f"{np.max(np.abs(w - deq) / bound)}x the bound"
    )
    h = np.asarray(
        jax.random.normal(jax.random.PRNGKey(5), (3, w.shape[1]), jnp.float32)
    )
    fused = np.asarray(qmatmul(jnp.asarray(h), QuantizedTensor(qt.q[0], qt.scale[0])))
    explicit = h @ deq[0]
    np.testing.assert_allclose(fused, explicit, rtol=1e-5, atol=1e-5)


def test_int8_weights_logits_allclose_and_serving(model_and_params):
    """End-to-end int8 contract: logits of the quantized model are
    allclose to fp within the bound's linear propagation (each matmul's
    weight error ≤ 1/254 of the channel max ⇒ ~1% activations at these
    dims), and a sharded serve with int8 weights runs to completion with
    full streams."""
    cfg, _, params = model_and_params
    qparams = quantize_params_int8(params)
    assert isinstance(qparams["layers"]["wq"], QuantizedTensor)
    assert not isinstance(qparams["embed"]["tokens"], QuantizedTensor)
    prompt = _prompts(1, seed=11, lo=10, hi=11)[0]

    def logits_of(p):
        from deepspeed_tpu.inference.decode import _forward_with_cache, init_cache

        cache = init_cache(cfg, 1, 16, dtype=jnp.float32)
        out, _ = _forward_with_cache(cfg, p, jnp.asarray(prompt[None]), cache, jnp.int32(0))
        return np.asarray(out)

    lf, lq = logits_of(params), logits_of(qparams)
    # ~1e-2 relative on the logit SCALE (max|logits|): 4 quantized matmuls
    # per layer × 2 layers, each contributing ≲ 1/254 relative weight error
    tol = 1e-2 * np.max(np.abs(lf))
    np.testing.assert_allclose(lq, lf, atol=tol)
    srv = _server(cfg, qparams, tp=_tp(2))
    outs = srv.serve([prompt], max_new_tokens=6)
    assert outs[0].size == prompt.size + 6 and srv.stats["finished"] == 1


# --- quantized all-reduce: the EQuARX exchange ------------------------------
def test_quantized_allreduce_allclose():
    """The quantized exchange vs the fp psum it replaces: two symmetric
    int8 stages bound the relative error at ~2/127 of the per-chunk max;
    assert well inside that (and exact shape/dtype preservation)."""
    degree = 4
    mesh = serving_mesh(degree)
    rs = np.random.RandomState(0)
    partials = jnp.asarray(rs.randn(degree, 3, 5, 16).astype(np.float32))
    from jax.sharding import PartitionSpec as P

    def run(fn):
        sm = shard_map(
            lambda xs: fn(xs[0]),
            mesh=mesh, in_specs=(P("model"),), out_specs=P(), check_vma=False,
        )
        return np.asarray(sm(partials))

    ref = run(lambda x: jax.lax.psum(x, "model"))
    got = run(lambda x: quantized_all_reduce(x, "model", degree))
    assert got.shape == ref.shape and got.dtype == ref.dtype
    scale = np.max(np.abs(ref))
    np.testing.assert_allclose(got, ref, atol=2.0 * scale * 2.0 / 127.0)
    # indivisible last dim falls back to the exact psum
    odd = jnp.asarray(rs.randn(degree, 3, 7).astype(np.float32))

    def run_odd(fn):
        sm = shard_map(
            lambda xs: fn(xs[0]),
            mesh=mesh, in_specs=(P("model"),), out_specs=P(), check_vma=False,
        )
        return np.asarray(sm(odd))

    np.testing.assert_array_equal(
        run_odd(lambda x: quantized_all_reduce(x, "model", degree)),
        run_odd(lambda x: jax.lax.psum(x, "model")),
    )


def test_quantized_allreduce_serving_allclose_contract(model_and_params):
    """Serving with quantized all-reduces completes with full streams; the
    contract is allclose-per-projection, so token streams are NOT asserted
    byte-identical — but the serve must finish, keep the dispatch budget,
    and report the knob in serve_stats."""
    cfg, _, params = model_and_params
    prompts = _prompts(3, seed=9)
    tel = CompileTelemetry()
    srv = _server(
        cfg, params, tp=_tp(4, quantized_allreduce=True), telemetry=tel,
    )
    outs = srv.serve(prompts, max_new_tokens=6)
    assert all(o.size == p.size + 6 for o, p in zip(outs, prompts))
    st = srv.serve_stats()
    assert st["tp_quantized_allreduce"] is True and st["finished"] == 3
    assert compiled_serving_programs(tel.stats()) <= 2
