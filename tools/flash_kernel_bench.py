"""The three flash-attention kernels alone, on the chip, at the training
cells' shapes (``deepspeed_tpu/ops/transformer/flash_attention.py``): forward
and backward of ``CALLS`` causal calls in one program, as a layer scan makes
them, entered as the model enters them (q, k, v ``[B, T, N * D]`` as a
projection writes them, reshaped for nothing to ``[B, T, N, D]`` at the
door), for

* ``8x12x1024x64``: GPT-2 125M, 8 sequences x 12 heads (``gpt2_125m_zero1_train``),
* ``8x25x1024x64``: GPT-2 XL, 8 x 25 a chip (``gpt2_xl_zero3_dp4_train``),
* ``1x64x1024x128``, ``1x32x4096x128``: heads of 128 (Llama / Mistral families;
  no cell trains one, so this is their only guard),

and prints, from the profiler's trace of that program, the microseconds a call
of ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` against the least
the chip could take (``benchmark/kernels/flash_attention.py::min_seconds``),
the three summed beside the milliseconds the whole forward + backward takes
on the host's clock and what the device spends of the difference in ``copy``
and ``transpose`` instructions (the head-major layout's price, where a
checkout pays one), and the seconds the program took to trace and to lower
(what every process pays at set-up, whatever the compilation cache holds). It
is how the constants at the head of the kernels' file were chosen: each
``--set`` runs the shapes once more with other values of them.

Run it on the parent's copy and on the change's side by side (``--root``: the
checkout whose ``deepspeed_tpu`` is imported; only the public
``flash_attention`` is called, so any checkout runs).

    chiprun -- python3 tools/flash_kernel_bench.py [--root DIR] [--shapes 8x12x1024x64,...] [--set _BLOCK_Q=256,_BLOCK_K=256 --set _MAX_HEADS=2 ...]
    python3 tools/flash_kernel_bench.py --rehearse      # tiny, on the CPU: the control flow only
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CALLS = 20
SHAPES = "8x12x1024x64,8x25x1024x64,1x64x1024x128,1x32x4096x128"
# the program's names -> the kinds of benchmark/kernels/flash_attention.py; the longest name first, a name is matched once
KERNELS = {"flash_bwd_dkv": "backward_dkv", "flash_bwd_dq": "backward_dq", "flash_fwd": "forward"}


def kernel_us(trace_dir: str, calls: int):
    """``({kernel: (microseconds a call, events a call)}, microseconds a call
    in copy and transpose instructions, their events a call)`` from the newest
    trace under ``trace_dir``."""
    from mixed_step_bench import device_ops  # the sibling tool's reading of a trace's device ops

    us, events = collections.Counter(), collections.Counter()
    for ms, n, text in device_ops(trace_dir, calls, top=None):
        name, _, rest = text.partition(" = ")
        kernel = next((k for k in KERNELS if k in name), None)
        if kernel and "custom-call" in text:
            us[kernel] += ms * 1e3
            events[kernel] += n
        elif re.match(r"\S+ (copy|transpose)\(", rest):
            us["moved"] += ms * 1e3
            events["moved"] += n
    return {k: (us[k], events[k]) for k in KERNELS}, us["moved"], events["moved"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=ROOT, help="the checkout whose deepspeed_tpu is measured")
    ap.add_argument("--shapes", default=SHAPES, help="BxNxTxD, comma-separated")
    ap.add_argument(
        "--set", action="append", default=[], metavar="NAME=INT,...",
        help="constants of the kernels' file to try in place of the file's, e.g. _BLOCK_Q=256,_BLOCK_K=256; may be given more than once",
    )
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp

    from benchmark import files
    from benchmark.kernels import flash_attention as roofline
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    module = sys.modules[flash_attention.__module__]  # the package rebinds the module's name to the function
    calls = 2 if args.rehearse else CALLS
    shapes = [(2, 3, 256, 64)] if args.rehearse else [tuple(int(n) for n in s.split("x")) for s in args.shapes.split(",")]
    peak = files.load_json(files.HERE, "peaks.json")["TPU v5 lite" if args.rehearse else jax.devices()[0].device_kind]
    tries = [{name: int(value) for name, value in (pair.split("=") for pair in t.split(","))} for t in args.set]
    the_files = {name: getattr(module, name) for t in tries for name in t}  # AttributeError: a checkout without the constant
    print(f"root {os.path.abspath(args.root)} on {jax.devices()[0].device_kind}", flush=True)
    for B, N, T, D in shapes:
        # [B, T, N * D] as a projection leaves it
        q, k, v, do = (
            jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(0), i), (B, T, N * D), jnp.bfloat16) for i in range(4)
        )
        for tried in tries or [{}]:
            for name, value in {**the_files, **tried}.items():
                setattr(module, name, value)
            used = ",".join(f"{name}={value}" for name, value in tried.items()) or "the file's"
            # the caller's blocks are upper limits, the default 512
            limits = {"block_" + name[-1].lower(): value for name, value in tried.items() if name.startswith("_BLOCK_") and value > 512}

            def many(q, k, v, do):
                # CALLS layers back to back in one program: forward, then the backward of a cotangent. Each
                # call's operands come from the call before (a small step along the gradient), or the
                # compiler lifts the kernels out of the loop.
                def attention(*qkv):
                    return flash_attention(*(x.reshape(B, T, N, D) for x in qkv), causal=True, **limits).reshape(B, T, N * D)

                def body(i, qkv):
                    o, vjp = jax.vjp(attention, *qkv)
                    return tuple(x - (1e-3 * g.astype(jnp.float32) + 1e-3 * o.astype(jnp.float32)).astype(x.dtype) for x, g in zip(qkv, vjp(do)))

                return jax.lax.fori_loop(0, calls, body, (q, k, v))

            t0 = time.perf_counter()
            traced = jax.jit(many).trace(q, k, v, do)
            t1 = time.perf_counter()
            lowered = traced.lower()
            t2 = time.perf_counter()
            program = lowered.compile()
            t3 = time.perf_counter()
            jax.block_until_ready(program(q, k, v, do))
            best = float("inf")
            for _ in range(1 if args.rehearse else 3):
                t = time.perf_counter()
                jax.block_until_ready(program(q, k, v, do))
                best = min(best, time.perf_counter() - t)
            line = (
                f"{B}x{N}x{T}x{D} {used}: forward + backward {best / calls * 1e3:7.3f} ms a call | "
                f"trace {t1 - t0:.3f} s lower {t2 - t1:.3f} s compile {t3 - t2:.2f} s"
            )
            if not args.rehearse:  # the CPU backend writes no device plane
                trace_dir = os.path.join(ROOT, ".benchmark_trace", "flash_kernel_bench")
                jax.profiler.start_trace(trace_dir)
                jax.block_until_ready(program(q, k, v, do))
                jax.profiler.stop_trace()
                kernels, moved_us, moved_n = kernel_us(trace_dir, calls)
                for kernel, (us, n) in kernels.items():
                    floor, bound = roofline.min_seconds(KERNELS[kernel], B * N, T, D, peak)
                    line += f"\n    {kernel:14s} x{n:3.1f} {us:8.1f} us a call, floor {floor * 1e6:6.1f} us ({bound}), {100 * floor * 1e6 / us:5.1f}%"
                line += (
                    f"\n    the three kernels {sum(us for us, _ in kernels.values()) / 1e3:7.3f} ms of the call's {best / calls * 1e3:7.3f} ms; "
                    f"copy + transpose x{moved_n:3.1f} {moved_us / 1e3:7.3f} ms"
                )
            print(line, flush=True)


if __name__ == "__main__":
    main()
