"""The ragged serving programs of the benchmark's configurations, lowered for
a described v5e (nothing compiles, nothing runs), and each attention kernel's
custom call held to what the benchmark's readers expect of it.

``benchmark/kernels/ragged_paged_attention.py::EVENTS`` tells the ragged
kernel by its signature, three ``s32`` scalar-prefetch operands (page table,
kv lengths, q lengths) in front of the row operand and the two page pools,
and ``trace_reduce.checked_kernel_events`` raises unless every whole execution
holds exactly ``num_layers`` such calls: the ``serve.`` / ``chat.`` / ``moe.``
``ragged_attn_*`` readers run that check in every traced run of the Mistral
and OLMoE cells. ``windowed_paged_attention.py`` finds the same kernel by its
``name=`` inside a scope. A PR that gives that kernel another operand, or adds
a second custom call of the same signature to a model that had none, makes
those cells' traced runs exit 1 (PR 38). So, for every configuration the
benchmark holds:

* every custom call that opens with three ``s32`` operands is named
  ``ragged_paged_attention`` and has the operand list the kernel had (the row
  operand, the sinks of a model that has them, two pools of one type), or is
  the latent kernel in a model with latent layers, and in no other;
* no other custom call opens with more than one ``s32`` operand;
* the calls a step: one a layer through the layer scan of a uniform model
  (``calls_per_step``; a looped model's stack is scanned once a pass: one
  call in each pass's scan, ``cache_layers`` a step), one a layer kind a
  period and leading layer in a model of several kinds, twice that in its
  wide program.

And since PR 63 (a sixth layer kind whose mixer is a convolution alone, a norm a
head on q and k, a partial last period run behind the scan), for the hybrid
configurations accepted before it: the parameter tree of each is what it was
(a fingerprint of paths, shapes and types recorded from the parent commit:
``data/accepted_hybrid_trees.json``), none names the new kind, the norm or a
remainder, and with none of the three a toy of each family traces to the same
text whether the step knows of them or not (``..._trace_nothing``).
"""

import dataclasses
import hashlib
import importlib
import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import files
from benchmark.kernels import ragged_paged_attention, windowed_paged_attention
from deepspeed_tpu.inference import decode, hybrid_decode
from deepspeed_tpu.inference.kv_pool import StateStore, heads_per_group, key_lanes, page_shapes, window_ring_pages
from deepspeed_tpu.models.config import cache_layers

SPEC = json.loads((pathlib.Path(files.ROOT) / "BENCHMARK.json").read_text())
CONFIGS = [c["name"] for c in SPEC["configs"]]
ACCEPTED = ["gpt2-125m", "gpt2-xl", "mistral-7b-v0.3-l16", "olmoe-1b-7b-0125-l12", "solar-open2-250b-l4-ep8", "mimo-v2.5-l7-ep16",
            "glm-4.7-flash-l16-ep8"]
LATENT = ["glm-4.7-flash-l16-ep8"]  # of the accepted, the ones with latent layers: no other of them may call the latent kernel
BF16, I32 = jnp.bfloat16, jnp.int32
# a training configuration has no serving geometry of its own: the Mistral cells'
DEFAULT_PAGED = {"page_size": 64, "max_slots": 16, "prefill_chunk": 128, "max_seq_len": 1024}


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _lowered_step(v5e, monkeypatch, name, width):
    """(config, the StableHLO text of its ragged step at its own sizes)."""
    for module in ("deepspeed_tpu.ops.transformer.decode_attention", "deepspeed_tpu.ops.transformer.latent_attention",
                   "deepspeed_tpu.moe.grouped_matmul", "deepspeed_tpu.moe.route_plan", "deepspeed_tpu.ops.transformer.linear_attention",
                   "deepspeed_tpu.ops.transformer.state_space"):
        importlib.import_module(module)
        if hasattr(sys.modules[module], "on_tpu"):  # NOT via attribute access: ops/transformer rebinds names
            monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)
    conf = files.load_json(files.ROOT, next(c["file"] for c in SPEC["configs"] if c["name"] == name))
    model, _ = files.build_model(conf)
    cfg = model.config
    paged = conf["engine"].get("init_inference", {}).get("paged_kv", DEFAULT_PAGED)
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = min(paged["max_seq_len"], cfg.max_seq_len) // page
    pages = rows * maxp + 1

    def on(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=v5e)

    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), I32)))
    params = jax.tree_util.tree_map(lambda a: on(a.shape), params)
    extra = ()
    layers = cache_layers(cfg)  # a looped model's num_loops x num_layers, a multi-kind model's softmax layers
    if getattr(cfg, "layer_types", None):
        shapes = hybrid_decode.state_shapes(cfg, rows)
        rings = (None, None)
        if cfg.layers_of("window"):
            ring = window_ring_pages(cfg.window, page, paged["prefill_chunk"])
            rings = tuple(on(s) for s in hybrid_decode.window_shapes(cfg, rows, page, ring))
        latent = on((cfg.layers_of("latent"), pages, page, key_lanes(cfg.latent_width))) if cfg.layers_of("latent") else None
        state = None if shapes.state is None else on(shapes.state, jnp.float32)  # a kind that keeps a tail alone has no state array
        new = ()  # PR 66's two kinds: latents and indexer keys under the page table, rings of latents
        if cfg.layers_of("sparse_latent") or cfg.layers_of("window_latent"):
            latent, index = (None if s is None else on(s) for s in hybrid_decode.paged_latent_shapes(cfg, pages, page))
            ring = window_ring_pages(cfg.window, page, paged["prefill_chunk"])
            new = (index, on(hybrid_decode.window_latent_shape(cfg, rows, page, ring)) if cfg.layers_of("window_latent") else None)
        extra = (StateStore(state, on(shapes.conv), *rings, latent, *new),)
    # the pool's own shapes: heads narrower than a lane tile share one (granite's 8 of 64, gpt2-125m's 12: two a page)
    v_head_dim = getattr(cfg, "v_head_dim", None) or cfg.head_dim
    f = heads_per_group(cfg.head_dim, v_head_dim, cfg.num_kv_heads)
    assert f == {"granite-4.0-h-micro": 2, "gpt2-125m": 2, "lfm2-24b-a2b-ep8": 2}.get(name, 1)
    if f > 1:  # such a pool goes to the kernel that walks live pages: reaching the grid fallback would raise
        monkeypatch.setattr(sys.modules["deepspeed_tpu.ops.transformer.decode_attention"], "_ragged_by_grid", None)
    k_pool, v_pool = (on(s) for s in page_shapes(layers, pages, cfg.num_kv_heads, page, cfg.head_dim, v_head_dim, f))
    step = decode.build_ragged_step(cfg, rows, width, page, attn_impl="pallas")
    rows_i32 = (on((rows,), I32),) * (3 if extra else 2)
    text = step.lower(params, on((rows, width), I32), k_pool, v_pool, *extra, on((rows, maxp), I32), *rows_i32).as_text()
    decode._paged_program_cache.clear()  # programs lowered for a described chip are nobody else's
    return cfg, text


_CALL = re.compile(r'stablehlo\.custom_call @tpu_custom_call\(([^)]*)\).*?kernel_name = "([\w.-]+)".*?: \(([^)]*)\) ->')


def _kernel_calls(text):
    """(kernel name, operand element types) of every Mosaic custom call in the program text."""
    calls = []
    for line in text.splitlines():
        if "@tpu_custom_call" not in line:
            continue
        m = _CALL.search(line)
        assert m, line[:300]
        types = [t.strip().rsplit("x", 1)[-1].rstrip(">") for t in re.findall(r"tensor<[^>]*>", m.group(3))]
        calls.append((m.group(2), types))
    return calls


def _expected_ragged_calls(cfg, width):
    if not getattr(cfg, "layer_types", None):
        # the layer scan's body: num_layers a step (ragged_paged_attention.calls_per_step). A looped model scans its stack a
        # pass: the narrow program's passes are ONE traced body scanned num_loops times (the carried index runs on), the wide
        # program's have a body each (a pass's first cache layer is the body's own constant)
        return 1 if width == 1 else cfg.num_loops
    return sum(k in ("softmax", "window") for k in _traced_layers(cfg)) * (1 if width == 1 else 2)


def _traced_layers(cfg):
    """The layers whose bodies a step's program holds: the leading ones, ONE period (the scan's body), the trailing ones."""
    return list(cfg.layer_types[: cfg.leading_dense_layers]) + list(cfg.period) + list(cfg.remainder)


@pytest.mark.parametrize("width", [1, 128])
@pytest.mark.parametrize("name", CONFIGS)
def test_attention_kernel_calls_are_what_the_benchmarks_readers_expect(v5e, monkeypatch, name, width):
    cfg, text = _lowered_step(v5e, monkeypatch, name, width)
    calls = _kernel_calls(text)
    assert calls, "no Mosaic kernel in the program"
    latent_layers = getattr(cfg, "layer_types", None) and cfg.layers_of("latent")
    ragged = [types for kernel, types in calls if kernel == windowed_paged_attention.KERNEL]
    for kernel, types in calls:
        leading = next(i for i, t in enumerate(types + ["-"]) if t != "i32")
        if kernel == windowed_paged_attention.KERNEL:
            # what EVENTS matches: three s32 in front; then the row operand, a model's sinks, the two pools
            sinks = ["f32"] if getattr(cfg, "window_sinks", False) and len(types) == 7 else []
            assert types == ["i32"] * 3 + ["bf16"] + sinks + ["bf16", "bf16"], (kernel, types)
        elif kernel == "latent_paged_attention":
            assert latent_layers and (name in LATENT or name not in ACCEPTED), f"{name} has no latent layer and must not call {kernel}"
            assert types == ["i32"] * 3 + ["bf16", "bf16"], types  # the row operand and ONE pool
        else:
            assert leading <= 1, f"{kernel} opens with {leading} s32 operands: the ragged kernel's readers would count it"
    assert len(ragged) == _expected_ragged_calls(cfg, width), (len(ragged), [k for k, _ in calls])
    if not getattr(cfg, "layer_types", None):
        assert ragged_paged_attention.calls_per_step(cache_layers(cfg)) == {"ragged": cfg.num_layers * cfg.num_loops}
    if latent_layers:
        assert sum(k == "latent_paged_attention" for k, _ in calls) == _traced_layers(cfg).count("latent") * (1 if width == 1 else 2)


def test_the_guard_knows_every_configuration():
    """A configuration a later PR adds is lowered and held above too (PR 45's
    ``laguna-s-2.1-l9-ep16`` came in through ``CONFIGS``, two cases); the seven
    accepted before it are named, and of them the one with latent layers."""
    assert set(LATENT) <= set(ACCEPTED) <= set(CONFIGS) and len(CONFIGS) > len(ACCEPTED)
    for name in ACCEPTED:
        conf = files.load_json(files.ROOT, next(c["file"] for c in SPEC["configs"] if c["name"] == name))
        assert ("latent" in (conf["model"]["kwargs"].get("layer_types") or ())) == (name in LATENT)


# --- PR 63: the conv kind, the head norm and the partial last period leave the accepted hybrid configurations alone -------------

TREES = json.loads((pathlib.Path(__file__).parent / "data" / "accepted_hybrid_trees.json").read_text())["trees"]


@pytest.mark.parametrize("name", sorted(TREES))
def test_an_accepted_hybrid_configurations_tree_is_what_it_was(name):
    """Paths, shapes and types of the adapter's ``init``, as recorded from the commit before the conv kind: no
    ``trailing`` leaves, no head norm's scales, no seventh stack; and the config names none of the three."""
    conf = files.load_json(files.ROOT, next(c["file"] for c in SPEC["configs"] if c["name"] == name))
    model, _ = files.build_model(conf)
    cfg = model.config
    assert cfg.remainder == () and cfg.qk_norm is None and "conv" not in cfg.layer_types
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), None))
    assert "trailing" not in shapes and "conv" not in shapes["periods"]
    tree = sorted((jax.tree_util.keystr(path), tuple(a.shape), str(a.dtype)) for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0])
    assert hashlib.sha256(repr(tree).encode()).hexdigest()[:16] == TREES[name]


def _toy_step_text(cfg):
    """The jaxpr of a toy's narrow step (``hybrid_forward``, four rows)."""
    from deepspeed_tpu.inference.kv_pool import PagePool
    from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM

    params = jax.eval_shape(lambda: HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    pool = PagePool(cfg, 9, 8, 4, max_seq_len=16, dtype=jnp.float32, prefill_chunk=8)
    i32 = lambda *shape: jnp.zeros(shape, I32)
    store = pool.states
    forward = lambda p: hybrid_decode.hybrid_forward(
        cfg, p, i32(4, 1), pool.cache.k_pages, pool.cache.v_pages, store.state, store.conv, i32(4, 2), i32(4), i32(4), i32(4), attn_impl="xla",
        window=None if store.window_k is None else (store.window_k, store.window_v), latent=store.latent,
    )
    return str(jax.make_jaxpr(forward)(params))


@pytest.mark.parametrize("family", ["solar_open2_config", "laguna_config", "granite_hybrid_config"])
def test_with_no_conv_layer_no_head_norm_and_no_remainder_the_new_code_traces_nothing(family, monkeypatch):
    """An accepted family's toy (delta-rule layers beside NoPE attention; rotary
    and window layers with a head gate behind a leading layer; state-space
    layers with a dense FFN) traces to the same text whether ``attn_heads``
    knows of the head norm or is what it was before (below, verbatim), and
    with every function of the conv kind and the trailing layers' FFN made to
    raise; with the norm set, or one more layer that makes a remainder, the
    text grows."""
    from deepspeed_tpu.models import hybrid_moe as hm
    from deepspeed_tpu.models.transformer import _rope

    cfg = getattr(hm, family)("tiny", dtype="float32")
    assert cfg.remainder == () and cfg.qk_norm is None and cfg.state_kind != "conv"
    traced = _toy_step_text(cfg)

    def attn_heads_before(cfg, kind, q, k, v, positions):
        NH, NKV, D, Dv = cfg.heads_of(kind), cfg.kv_heads_of(kind), cfg.head_dim, cfg.v_head_dim
        q, k, v = (a.reshape(a.shape[:-1] + shape) for a, shape in zip((q, k, v), ((NH, D), (NKV, D), (NKV, Dv))))
        if cfg.position == "rope":
            scaled = cfg.rope_frequencies(kind)
            if scaled is not None:
                q, k = (hm._rope_scaled(a, positions, *scaled) for a in (q, k))
            else:
                theta = cfg.window_rope_theta if kind == "window" else cfg.rope_theta
                q, k = (_rope(a, positions, theta, cfg.rope_dim_of(kind)) for a in (q, k))
        if cfg.attn_value_scale != 1.0:
            v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)
        return q, k, v

    def not_this_model(*args, **kwargs):
        raise AssertionError("a function of the conv kind was traced for a model without a conv layer")

    monkeypatch.setattr(hm, "attn_heads", lambda cfg, kind, q, k, v, positions, p=None: attn_heads_before(cfg, kind, q, k, v, positions))
    for name in ("conv_inputs", "gated_conv", "shifted_tail", "conv_output"):
        monkeypatch.setattr(hm, name, not_this_model)
    assert _toy_step_text(cfg) == traced
    monkeypatch.undo()
    if "softmax" in cfg.layer_types and family != "granite_hybrid_config":
        assert len(_toy_step_text(dataclasses.replace(cfg, qk_norm="head"))) > len(traced)
    # at least two whole periods and one layer more: the scan's body is the same, and one trailing layer's is traced behind it
    types = cfg.layer_types[: cfg.leading_dense_layers] + cfg.period * max(cfg.num_periods, 2) + cfg.period[:1]
    longer = dataclasses.replace(cfg, num_layers=len(types), layer_types=types)
    assert longer.remainder == cfg.period[:1] and longer.period == cfg.period and len(_toy_step_text(longer)) > len(traced)


# --- PR 66: two latent kinds, a rescale, a gate on latent layers, two more pools: every accepted family's toy steps are what they were ---

TOY_STEPS = json.loads((pathlib.Path(__file__).parent / "data" / "accepted_toy_steps.json").read_text())["steps"]


@pytest.mark.parametrize("family", sorted({name.split("/")[0] for name in TOY_STEPS}))
def test_an_accepted_familys_toy_steps_trace_to_what_they_did(family, monkeypatch):
    """The narrow and the wide step of the family's tiny preset trace to the
    text they traced to on the commit before the ``sparse_latent`` and
    ``window_latent`` kinds (a fingerprint a width, recorded there): the
    generalised latent functions (``latent_dims``, a rescale of 1.0, a gate no
    accepted latent layer has), the store's two new fields (None: no
    parameter) and the new kinds' pools put nothing into, and take nothing out
    of, a program that names neither kind."""
    from deepspeed_tpu.inference.kv_pool import PagePool
    from deepspeed_tpu.models import hybrid_moe as hm

    monkeypatch.setattr(decode, "DENSE_TOKEN_TILE", 16)
    cfg = getattr(hm, family)("tiny", dtype="float32")
    assert not {"sparse_latent", "window_latent"} & set(cfg.layer_types)
    params = jax.eval_shape(lambda: hm.HybridMoETransformerLM(cfg).init(jax.random.PRNGKey(0), None))
    for width in (1, 16):
        pool = PagePool(cfg, 9, 8, 4, max_seq_len=16, dtype=jnp.float32, prefill_chunk=8)
        i32 = lambda *shape: jnp.zeros(shape, I32)
        st = pool.states
        assert st.index is None and st.window_latent is None
        forward = lambda p: hybrid_decode.hybrid_forward(
            cfg, p, i32(4, width), pool.cache.k_pages, pool.cache.v_pages, st.state, st.conv, i32(4, 2), i32(4), i32(4), i32(4), attn_impl="xla",
            window=None if st.window_k is None else (st.window_k, st.window_v), latent=st.latent,
        )
        text = str(jax.make_jaxpr(forward)(params))
        assert hashlib.sha256(text.encode()).hexdigest()[:12] == TOY_STEPS[f"{family}/w{width}"], (family, width)
