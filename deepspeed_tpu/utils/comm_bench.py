"""Collective benchmark sweep (``ds_bench`` CLI).

Counterpart of the reference's ``bin/ds_bench`` → comm benchmark: times the
core collectives (all_reduce / all_gather / reduce_scatter / all_to_all)
over the live device mesh across a size sweep and prints achieved bus
bandwidth (same algbw/busbw accounting as
``deepspeed/utils/comms_logging.py get_bw``).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from jax import shard_map


def _bw_gb(op: str, size_bytes: int, seconds: float, n: int) -> float:
    """Bus bandwidth in GB/s (ring-algorithm accounting, comms_logging.get_bw)."""
    if seconds == 0:
        return 0.0
    algbw = size_bytes / seconds
    if op in ("all_reduce",):
        busbw = algbw * (2 * (n - 1) / n)
    elif op in ("all_gather", "reduce_scatter", "all_to_all"):
        busbw = algbw * ((n - 1) / n)
    else:
        busbw = algbw
    return busbw / 1e9


def run_sweep(sizes_mb, trials: int = 5, warmups: int = 2):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("x",))
    results = []

    ops = {
        "all_reduce": lambda x: jax.lax.psum(x, "x"),
        "all_gather": lambda x: jax.lax.all_gather(x, "x"),
        "reduce_scatter": lambda x: jax.lax.psum_scatter(x, "x", tiled=True),
        "all_to_all": lambda x: jax.lax.all_to_all(
            x.reshape(n, -1), "x", split_axis=0, concat_axis=0
        ),
    }
    for size_mb in sizes_mb:
        elems = int(size_mb * 1e6 / 4)
        elems = max(elems - elems % (n * n), n * n)
        for name, op in ops.items():
            fn = jax.jit(
                shard_map(
                    op,
                    mesh=mesh,
                    in_specs=P("x"),
                    out_specs=P("x") if name != "all_reduce" else P(None),
                    check_vma=False,
                )
            )
            x = jax.device_put(
                jnp.ones((elems,), jnp.float32), NamedSharding(mesh, P("x"))
            )
            for _ in range(warmups):
                fn(x).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(trials):
                out = fn(x)
            out.block_until_ready()
            dt = (time.perf_counter() - t0) / trials
            results.append(
                {
                    "op": name,
                    "size_mb": size_mb,
                    "time_ms": dt * 1e3,
                    "busbw_gb_s": _bw_gb(name, elems * 4, dt, n),
                }
            )
    return results


def run_overlap_bench(size_mb: float = 16, compute_dim: int = 1024,
                      compute_iters: int = 8, trials: int = 5, warmups: int = 2):
    """Comm/compute overlap microbenchmark (ISSUE 5): wall time of a
    compute-only program (a scan of local matmuls — the stand-in for a
    layer's MXU work), a collective-only program (one all-gather, the
    stand-in for the next layer's ZeRO-3 param fetch), and one program
    containing BOTH with no data dependency between them — the shape the
    pipelined layer scan creates, which the scheduler is free to overlap.

    ``overlap_fraction`` is how much of the smaller leg disappeared into
    the larger one: (t_compute + t_collective - t_both) / min(t_compute,
    t_collective), clipped to [0, 1]. 1.0 = the cheaper leg is fully
    hidden; 0.0 = the runtime serialized them (what the ``overlap``
    analysis pass flags statically). This is the reproducible backing for
    PERF.md's hidden-vs-exposed claims: the same three programs, timed.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("x",))
    elems = max(int(size_mb * 1e6 / 4) // n * n, n)

    x = jax.device_put(jnp.ones((elems,), jnp.float32), NamedSharding(mesh, P("x")))
    w = jax.device_put(
        jnp.eye(compute_dim, dtype=jnp.float32) * 0.999,
        NamedSharding(mesh, P(None, None)),
    )

    def compute_leg(w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, w, None, length=compute_iters)
        return out

    def collective_leg(x):
        return shard_map(
            lambda t: jax.lax.all_gather(t, "x", tiled=True),
            mesh=mesh, in_specs=P("x"), out_specs=P(None), check_vma=False,
        )(x)

    programs = {
        "compute_only": (jax.jit(compute_leg), (w,)),
        "collective_only": (jax.jit(collective_leg), (x,)),
        # no data dependency between the legs: the overlapped shape
        "overlapped": (jax.jit(lambda w, x: (compute_leg(w), collective_leg(x))), (w, x)),
    }
    times = {}
    for name, (fn, args) in programs.items():
        for _ in range(warmups):
            jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(trials):
            out = fn(*args)
        jax.block_until_ready(out)
        times[name] = (time.perf_counter() - t0) / trials
    t_c, t_x, t_b = times["compute_only"], times["collective_only"], times["overlapped"]
    saved = t_c + t_x - t_b
    frac = max(0.0, min(1.0, saved / max(min(t_c, t_x), 1e-12)))
    return {
        "devices": n,
        "size_mb": size_mb,
        "compute_only_ms": t_c * 1e3,
        "collective_only_ms": t_x * 1e3,
        "overlapped_ms": t_b * 1e3,
        "overlap_fraction": frac,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="deepspeed_tpu collective benchmark")
    parser.add_argument("--sizes-mb", type=float, nargs="+", default=[1, 16, 64])
    parser.add_argument("--trials", type=int, default=5)
    parser.add_argument(
        "--overlap", action="store_true",
        help="comm/compute overlap mode: compute-only vs collective-only vs "
        "one overlapped program (ISSUE 5 microbenchmark)",
    )
    parser.add_argument("--compute-iters", type=int, default=8)
    args = parser.parse_args(argv)
    if args.overlap:
        for size_mb in args.sizes_mb:
            r = run_overlap_bench(size_mb, compute_iters=args.compute_iters,
                                  trials=args.trials)
            print(
                f"devices={r['devices']} size={r['size_mb']:.1f}MB "
                f"compute={r['compute_only_ms']:.2f}ms "
                f"collective={r['collective_only_ms']:.2f}ms "
                f"overlapped={r['overlapped_ms']:.2f}ms "
                f"overlap_fraction={r['overlap_fraction']:.2f}"
            )
        return 0
    results = run_sweep(args.sizes_mb, trials=args.trials)
    print(f"{'op':16s} {'size(MB)':>9s} {'time(ms)':>10s} {'busbw(GB/s)':>12s}")
    for r in results:
        print(
            f"{r['op']:16s} {r['size_mb']:9.1f} {r['time_ms']:10.3f} {r['busbw_gb_s']:12.2f}"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
