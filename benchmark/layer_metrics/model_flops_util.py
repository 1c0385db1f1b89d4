"""Model FLOP/s utilization of training: tokens/s/chip of this run's window
times the operations the forward and backward passes need per token,
``6 N + 12 L H T`` (N parameters from the engine; the second term is
attention over T positions), over the chip's bf16 peak. Recomputation under
``remat`` is not counted. The arithmetic is ``bench.py``'s
``_mfu_vs_north_star``, copied."""


def value(trace, counters, cell):
    if "n_params" not in counters or cell["peak"] is None:
        return None
    per_token = 6 * counters["n_params"] + 12 * counters["model"]["num_layers"] * counters["model"]["hidden_size"] * counters["seq_len"]
    per_chip = counters["tokens"] / counters["window_s"] / cell["chips"]
    return 100.0 * per_chip * per_token / cell["peak"]["bf16_flops"]
