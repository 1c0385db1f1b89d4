"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              one TPU chip: the two main paths
    python chip_smoke.py --chips 4    four chips: only the sharded paths and
                                      their one-chip twins
    python chip_smoke.py --rehearse   the same control flow at a tiny size on
                                      the CPU (add --chips 4 for four virtual
                                      devices); never claims a TPU

One process, which holds the chip for the whole run (a chip belongs to one
process at a time). Both phases go through the entry points a user calls, at
the full width and depth of a preset the repo ships, with weights and data
made from ``--seed``:

* trainer: ``ds.initialize(TransformerLM(gpt2_config("125m")))``, bf16,
  ZeRO-1, Adam, clipping 1.0, micro-batch 8, flash attention — the
  ``loss = engine(batch); engine.backward(loss); engine.step()`` loop;
* server: ``ds.init_inference(TransformerLM(llama_config("1b")))`` (22
  layers, 32 q heads over 4 kv heads) with the paged KV pool, then
  ``engine.serve`` on eight ragged requests.

Nothing is caught: a phase that raises, an assertion that fails or a backend
that is not a TPU ends the run with a traceback and a non-zero exit code.
Every printed time ends in ``block_until_ready`` on the step's outputs
(``serve`` and ``generate`` return host arrays, which is the same wait);
they are builder's notes, not a benchmark. The last line of standard output
is one JSON object, ``{"ok": true, "device": {...}}``, as JAX reports the
device.
"""

from __future__ import annotations

import argparse
import gc
import itertools
import json
import os
import re
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

BF16_LOSS_ATOL = 3e-2  # two bf16 roundings of a loss near ln(vocab) ~ 10.8
BF16_ATTN_TOL = 2e-2  # attention outputs are O(1) bf16 values (8 mantissa bits)


def sizes(rehearse: bool) -> SimpleNamespace:
    """What runs: the shipped presets, or their tiny CPU stand-ins."""
    from deepspeed_tpu.models import gpt2_config, llama_config

    if rehearse:
        return SimpleNamespace(
            train_name="gpt2 tiny",
            train_model=lambda **kw: gpt2_config(
                "tiny", num_layers=2, max_seq_len=128, remat=False, **kw
            ),
            global_batch=4,
            train_steps=5,
            serve_name="llama tiny",
            serve_model=llama_config(
                "tiny", num_layers=2, num_kv_heads=4, vocab_size=1024, max_seq_len=256
            ),
            paged={"page_size": 8, "max_slots": 4, "prefill_chunk": 8},
            prompt_lens=[6, 20, 6, 20, 6, 20, 6, 20],
            budgets=[4, 6, 8, 10, 12, 14, 16, 5],
        )
    return SimpleNamespace(
        train_name="gpt2 125m",
        train_model=lambda **kw: gpt2_config("125m", max_seq_len=1024, remat=False, **kw),
        global_batch=8,
        train_steps=6,
        serve_name="llama 1b",
        serve_model=llama_config("1b"),
        paged={"page_size": 64, "max_slots": 8, "prefill_chunk": 128},
        # four lengths twice: generate() compiles per (batch, length), so the
        # reference costs four program pairs, not eight. 192 + 64 = 256 also
        # sends generate through the dense decode kernel.
        prompt_lens=[32, 96, 192, 256, 32, 96, 192, 256],
        budgets=[16, 24, 32, 40, 48, 56, 64, 20],
    )


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def memory_stat(key: str) -> list:
    """One ``memory_stats()`` entry per device (None on the CPU backend,
    which keeps no stats)."""
    return [(d.memory_stats() or {}).get(key) for d in jax.devices()]


def release() -> None:
    """Drop what the finished phase left on the device before the next one."""
    import deepspeed_tpu.parallel.mesh as mesh_mod

    gc.collect()
    jax.clear_caches()
    mesh_mod.reset_topology()


# ---------------------------------------------------------------------------
# trainer


def train_config(stage: int, micro: int, mesh_data: int | None = None) -> dict:
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adam", "params": {"lr": 3e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
    }
    if mesh_data is not None:
        config["mesh"] = {"data": mesh_data}
    return config


def fixed_batch(vocab: int, rows: int, seq: int, seed: int) -> dict:
    """One batch, repeated every step: data that can be learned."""
    toks = np.random.RandomState(seed).randint(0, vocab, (rows, seq + 1)).astype(np.int32)
    return {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}


def run_trainer(model_cfg, config: dict, batch: dict, steps: int):
    """``steps`` optimizer steps through the public loop. Returns the engine,
    the losses, and each step's wall time (the first one compiles)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM

    engine, _, _, _ = ds.initialize(model=TransformerLM(model_cfg), config=config)
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        jax.block_until_ready((loss, engine.get_params()))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return engine, losses, times


def check_losses(losses: list) -> None:
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall on a repeated batch: {losses}"


def check_compiled_once(stats: dict) -> None:
    for name, rec in stats.items():
        assert rec["compiles"] == (1 if rec["dispatches"] else 0), (
            f"program {name} compiled {rec['compiles']}x over {rec['dispatches']} dispatches"
        )


def check_kernel_in(text: str, program: str, on_chip: bool) -> bool:
    """The Pallas kernel is in the program exactly when this is a TPU: never
    the interpreter on the chip (and never a compiled kernel off it)."""
    has_kernel = "tpu_custom_call" in text
    assert has_kernel == on_chip, (
        f"{program}: tpu_custom_call {'missing from' if on_chip else 'present in'} the lowered program"
    )
    return has_kernel


def phase_trainer(sz, seed: int, on_chip: bool) -> None:
    model_cfg = sz.train_model()
    batch = fixed_batch(model_cfg.vocab_size, sz.global_batch, model_cfg.max_seq_len, seed)
    engine, losses, times = run_trainer(
        model_cfg, train_config(stage=1, micro=sz.global_batch), batch, sz.train_steps
    )
    check_losses(losses)
    stats = engine.compile_stats()
    check_compiled_once(stats)
    assert stats["fused_step"]["dispatches"] == sz.train_steps, stats
    has_kernel = check_kernel_in(engine.program_text("fused_step"), "fused_step", on_chip)
    n_params = engine.num_parameters()
    del engine
    release()

    # the same model and seed through the plain einsum attention: the flash
    # kernel's first-step loss (computed before any update) must agree
    plain, plain_losses, _ = run_trainer(
        sz.train_model(flash_attention=False),
        train_config(stage=1, micro=sz.global_batch),
        batch,
        1,
    )
    assert "tpu_custom_call" not in plain.program_text("fused_step")
    del plain
    release()
    gap = abs(losses[0] - plain_losses[0])
    assert gap <= BF16_LOSS_ATOL, f"flash {losses[0]} vs einsum {plain_losses[0]} first loss"
    report(
        "trainer",
        model=sz.train_name,
        layers=model_cfg.num_layers,
        hidden=model_cfg.hidden_size,
        seq=model_cfg.max_seq_len,
        micro_batch=sz.global_batch,
        params=n_params,
        zero_stage=1,
        losses=losses,
        einsum_first_loss=plain_losses[0],
        flash_vs_einsum_first_loss_gap=gap,
        flash_kernel_in_step=has_kernel,
        programs={k: v["compiles"] for k, v in stats.items() if v["dispatches"]},
        cold_step_s=times[0],
        warm_step_s=times[1:],
        peak_bytes_in_use=memory_stat("peak_bytes_in_use"),
    )


# ---------------------------------------------------------------------------
# server


def make_requests(sz, seed: int):
    rs = np.random.RandomState(seed + 1)
    vocab = sz.serve_model.vocab_size
    prompts = [rs.randint(0, vocab, (n,)).astype(np.int32) for n in sz.prompt_lens]
    return prompts, list(sz.budgets)


def build_server(sz, tp: int = 1):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM

    kwargs = {"dtype": "bf16", "paged_kv": dict(sz.paged)}
    if tp > 1:
        kwargs["tensor_parallel"] = {"tp_size": tp}
    t0 = time.perf_counter()
    engine = ds.init_inference(TransformerLM(sz.serve_model), **kwargs)
    engine.init_params(np.zeros((1, 8), np.int32))  # weights from the engine's seeded rng
    return engine, time.perf_counter() - t0


def timed_serve(engine, prompts, budgets):
    t0 = time.perf_counter()
    outs = engine.serve(prompts, max_new_tokens=budgets)
    outs = [np.asarray(o) for o in outs]
    return outs, time.perf_counter() - t0


def check_streams(outs, prompts, budgets) -> None:
    for i, (out, prompt, budget) in enumerate(zip(outs, prompts, budgets)):
        assert out.shape == (len(prompt) + budget,), (
            f"request {i}: {out.shape[0]} tokens, expected {len(prompt)} + {budget}"
        )
        assert np.array_equal(out[: len(prompt)], prompt), f"request {i}: prompt not echoed"


def agreement(outs, refs, prompts) -> list:
    """Per request: the share of generated tokens equal to the reference's
    and the index of the first one that differs (None = none)."""
    rows = []
    for out, ref, prompt in zip(outs, refs, prompts):
        same = out[len(prompt):] == ref[len(prompt):]
        rows.append(
            {
                "equal_share": float(same.mean()),
                "first_divergence": None if same.all() else int(np.argmin(same)),
            }
        )
    return rows


def check_first_tokens(rows: list, versus: str) -> None:
    """Two programs that compute the same step (another attention back end,
    another all-reduce order, the other window width) round differently in
    bf16 and may leave a near-tie differently somewhere down a stream; a
    stream that is wrong from its first token is a bug."""
    wrong = [i for i, r in enumerate(rows) if r["first_divergence"] == 0]
    assert not wrong, f"first generated token differs from {versus} for requests {wrong}"


def serve_repeatedly(engine, prompts, budgets, on_chip: bool) -> dict:
    """Serve the requests cold, warm, and warm again; the checks every
    server leg shares. Returns the cold streams and what to report.

    The same schedule must give the same bytes: the two warm serves. The
    cold serve runs another schedule (nothing in the prefix cache yet, so
    whole prompts prefill), and on the chip a step served by the wide
    ragged program rounds differently from the same step served by the
    narrow one — so cold against warm is held to the first token only."""
    from deepspeed_tpu.inference.scheduler import compiled_serving_programs

    outs, cold_s = timed_serve(engine, prompts, budgets)
    check_streams(outs, prompts, budgets)
    warm, warm_s = timed_serve(engine, prompts, budgets)
    check_streams(warm, prompts, budgets)
    again, _ = timed_serve(engine, prompts, budgets)
    for i, (a, b) in enumerate(zip(warm, again)):
        assert np.array_equal(a, b), f"request {i}: the same serve twice is not byte-identical"
    warm_vs_cold = agreement(warm, outs, prompts)
    check_first_tokens(warm_vs_cold, "the cold serve")
    stats = engine.compile_stats()
    check_compiled_once(stats)
    assert compiled_serving_programs(stats) <= 2, sorted(stats)
    serving = sorted(k for k in stats if k.startswith("paged_"))
    kernels = {k: check_kernel_in(engine.program_text(k), k, on_chip) for k in serving}
    sstats = engine.serve_stats()
    assert sstats["used_pages"] == 0, f"pool not drained: {sstats['used_pages']} pages in use"
    return {
        "outs": outs,
        "fields": dict(
            requests=len(prompts),
            prompt_lens=[len(p) for p in prompts],
            budgets=budgets,
            serving_programs=serving,
            pallas_kernel_in_program=kernels,
            ragged_steps=sstats["ragged_steps"],
            prefix_hit_rate=sstats["prefix"]["prefix_hit_rate"],
            warm_vs_cold=warm_vs_cold,
            cold_serve_s=cold_s,
            warm_serve_s=warm_s,
            warm_generated_tokens=int(sum(budgets)),
        ),
    }


def generate_reference(engine, prompts, budgets):
    """``engine.generate`` (dense KV cache, XLA attention) per request,
    batched by prompt length; returns streams cut to each budget."""
    refs = [None] * len(prompts)
    horizon = max(budgets)
    for n in sorted({len(p) for p in prompts}):
        idx = [i for i, p in enumerate(prompts) if len(p) == n]
        out = np.asarray(
            engine.generate(jnp.asarray(np.stack([prompts[i] for i in idx])), max_new_tokens=horizon)
        )
        for row, i in zip(out, idx):
            refs[i] = row[: n + budgets[i]]
    return refs


def attention_parity(sz, seed: int) -> dict:
    """``ragged_paged_attention`` Pallas vs XLA on seeded inputs at the
    server's two window shapes: the outputs of live rows and slots, and the
    bytes both leave in every page but the trash page (layer 1 of two). With
    the server's heads on the server's pool (heads narrower than a lane tile
    share one: ``kv_pool.heads_per_group``), with the same heads one to a page,
    and with the same widths cut into heads of 128: pages of whole lanes take
    the kernel that walks a row's live pages, narrower ones the grid over the
    page table."""
    from deepspeed_tpu.inference.kv_pool import heads_per_group, page_shapes
    from deepspeed_tpu.ops.transformer.paged_attention import ragged_paged_attention

    cfg, paged = sz.serve_model, sz.paged
    rows, page = paged["max_slots"], paged["page_size"]
    maxp = cfg.max_seq_len // page
    n_pages = rows * maxp + 1
    rs = np.random.RandomState(seed + 2)
    table = jnp.asarray(1 + rs.permutation(rows * maxp).reshape(rows, maxp), jnp.int32)
    worst = {}
    whole_lanes = max(1, 128 // cfg.head_dim)  # heads that make one head of 128
    # (query heads, kv heads, head width, kv heads a page)
    layouts = {(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, f)
               for f in (1, heads_per_group(cfg.head_dim, cfg.head_dim, cfg.num_kv_heads))}
    if cfg.num_kv_heads % whole_lanes == 0:
        layouts.add((cfg.num_heads // whole_lanes, cfg.num_kv_heads // whole_lanes, cfg.head_dim * whole_lanes, 1))
    for (heads, kv_heads, head_dim, f), width in itertools.product(sorted(layouts), (1, paged["prefill_chunk"])):
        shape, _ = page_shapes(2, n_pages, kv_heads, page, head_dim, head_dim, f)
        k_pages = jnp.asarray(rs.randn(*shape), jnp.bfloat16)
        v_pages = jnp.asarray(rs.randn(*shape), jnp.bfloat16)
        q = jnp.asarray(rs.randn(rows, width, heads, head_dim), jnp.bfloat16)
        k_new, v_new = (
            jnp.asarray(rs.randn(rows, width, kv_heads, head_dim), jnp.bfloat16) for _ in range(2)
        )
        q_lens = rs.randint(1, width + 1, (rows,)).astype(np.int32)
        q_lens[-1] = 0  # a dead row
        kv_lens = np.where(
            q_lens > 0, q_lens + rs.randint(0, cfg.max_seq_len - width, (rows,)), 0
        ).astype(np.int32)
        got = {
            impl: jax.device_get(
                ragged_paged_attention(
                    q, k_new, v_new, k_pages, v_pages, 1, table, jnp.asarray(kv_lens),
                    jnp.asarray(q_lens), impl=impl,
                )
            )
            for impl in ("pallas", "xla")
        }
        for pool in (1, 2):  # the same bytes in every page but the trash page, in both layers
            assert np.array_equal(got["pallas"][pool][:, 1:], got["xla"][pool][:, 1:])
        live = np.arange(width)[None, :] < q_lens[:, None]
        a, b = (got[impl][0].astype(np.float32)[live] for impl in ("pallas", "xla"))
        assert np.isfinite(a).all() and np.isfinite(b).all()
        np.testing.assert_allclose(a, b, atol=BF16_ATTN_TOL, rtol=BF16_ATTN_TOL)
        worst[f"d{head_dim}x{f}_w{width}"] = float(np.abs(a - b).max())
    return worst


def phase_server(sz, seed: int, on_chip: bool) -> None:
    prompts, budgets = make_requests(sz, seed)
    engine, build_s = build_server(sz)
    served = serve_repeatedly(engine, prompts, budgets, on_chip)
    refs = generate_reference(engine, prompts, budgets)
    rows = agreement(served["outs"], refs, prompts)
    check_first_tokens(rows, "engine.generate")
    del engine
    release()
    report(
        "server",
        model=sz.serve_name,
        layers=sz.serve_model.num_layers,
        hidden=sz.serve_model.hidden_size,
        heads=[sz.serve_model.num_heads, sz.serve_model.num_kv_heads],
        build_s=build_s,
        **served["fields"],
        vs_generate=rows,
        pallas_vs_xla_max_abs_diff=attention_parity(sz, seed),
        peak_bytes_in_use=memory_stat("peak_bytes_in_use"),
    )


# ---------------------------------------------------------------------------
# four chips: the sharded paths against their one-chip twins


def check_spread(params, chips: int) -> int:
    """Every large parameter is cut into ``chips`` pieces on ``chips``
    devices. Returns how many were checked."""
    large = [p for p in jax.tree_util.tree_leaves(params) if p.size >= 100_000]
    assert large
    for p in large:
        shards = p.addressable_shards
        assert len({s.device for s in shards}) == chips, f"param {p.shape}: {len(shards)} shards"
        assert sum(s.data.size for s in shards) == p.size, f"param {p.shape} is replicated"
    return len(large)


def phase_trainer_sharded(sz, seed: int, chips: int) -> None:
    from deepspeed_tpu.parallel.mesh import initialize_topology
    from deepspeed_tpu.runtime.config import MeshConfig

    model_cfg = sz.train_model()
    batch = fixed_batch(model_cfg.vocab_size, sz.global_batch, model_cfg.max_seq_len, seed)
    steps = 3
    engine, losses, times = run_trainer(
        model_cfg,
        train_config(stage=3, micro=sz.global_batch // chips, mesh_data=chips),
        batch,
        steps,
    )
    # three steps are held to the one-device twin, not to a falling loss
    # (the 125M trajectory dips, rises and only then falls: see the trainer phase)
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    check_compiled_once(engine.compile_stats())
    # state really is spread: code that has only seen one chip may leave
    # everything on device 0
    n_large = check_spread(engine.get_params(), chips)
    in_use = memory_stat("bytes_in_use")
    if None not in in_use:  # the CPU backend keeps no memory stats
        assert max(in_use) <= 2 * min(in_use), f"device memory is lopsided: {in_use}"
    del engine
    release()

    # the same model and global batch on a one-device mesh
    initialize_topology(MeshConfig(data=1), devices=jax.devices()[:1])
    twin, twin_losses, _ = run_trainer(
        model_cfg, train_config(stage=3, micro=sz.global_batch, mesh_data=1), batch, steps
    )
    assert twin.mesh.devices.size == 1
    del twin
    release()
    gaps = [abs(a - b) for a, b in zip(losses, twin_losses)]
    assert max(gaps) <= BF16_LOSS_ATOL, f"dp{chips} {losses} vs one device {twin_losses}"
    report(
        "trainer_sharded",
        zero_stage=3,
        mesh={"data": chips},
        global_batch=sz.global_batch,
        losses=losses,
        one_device_losses=twin_losses,
        max_loss_gap=max(gaps),
        large_params_sharded=n_large,
        bytes_in_use_per_device=in_use,
        cold_step_s=times[0],
        warm_step_s=times[1:],
        peak_bytes_in_use=memory_stat("peak_bytes_in_use"),
    )


def phase_server_sharded(sz, seed: int, chips: int, on_chip: bool) -> None:
    prompts, budgets = make_requests(sz, seed)
    engine, build_s = build_server(sz, tp=chips)
    served = serve_repeatedly(engine, prompts, budgets, on_chip)
    assert engine.serve_stats()["tp_degree"] == chips
    kv = next(e for e in engine.memory_report(enforce=False)["entries"] if e["name"] == "kv_pages")
    assert kv["per_chip_bytes"] * chips == kv["global_bytes"], kv
    del engine
    release()

    twin, _ = build_server(sz, tp=1)
    twin_served = serve_repeatedly(twin, prompts, budgets, on_chip)
    del twin
    release()
    rows = agreement(served["outs"], twin_served["outs"], prompts)
    check_first_tokens(rows, "tp=1")
    report(
        "server_sharded",
        tp=chips,
        build_s=build_s,
        **served["fields"],
        kv_bytes_per_chip=kv["per_chip_bytes"],
        kv_total_bytes=kv["global_bytes"],
        vs_tp1=rows,
        tp1_warm_serve_s=twin_served["fields"]["warm_serve_s"],
        peak_bytes_in_use=memory_stat("peak_bytes_in_use"),
    )


# ---------------------------------------------------------------------------


def count_cache_events() -> dict:
    counts = {"hits": 0, "misses": 0}

    def listen(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    if args.rehearse:
        # virtual CPU devices, as many as the chips rehearsed; the flag only
        # shapes the host platform and is read when its client is created
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={args.chips}".strip()

    from deepspeed_tpu.profiling import use_compile_cache

    cache_dir = use_compile_cache()
    cache_events = count_cache_events()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    wanted = "cpu" if args.rehearse else "tpu"
    if device["platform"] != wanted:
        raise RuntimeError(f"chip_smoke needs a {wanted} backend, JAX found {device}")
    if device["count"] != args.chips:
        raise RuntimeError(f"--chips {args.chips} but JAX found {device}")
    on_chip = not args.rehearse
    report("start", device=device, chips=args.chips, seed=args.seed, rehearsal=args.rehearse,
           jax=jax.__version__, compile_cache_dir=cache_dir)

    sz = sizes(args.rehearse)
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_trainer(sz, args.seed, on_chip)
        phase_server(sz, args.seed, on_chip)
    else:
        phase_trainer_sharded(sz, args.seed, args.chips)
        phase_server_sharded(sz, args.seed, args.chips, on_chip)
    report("done", wall_s=time.perf_counter() - t0, compile_cache=cache_events)

    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "passed", "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
