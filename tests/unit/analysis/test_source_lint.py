"""AST lint unit tests: each rule fires on its minimal bad snippet, stays
quiet on the sanctioned idiom, honors pragmas — and the library itself
lints clean (the CI gate ``tools/lint.sh`` enforces: error findings under
``deepspeed_tpu/`` fail, ``tests/`` findings are warn-only)."""

from __future__ import annotations

import os
import textwrap

import pytest

from deepspeed_tpu.analysis.source_lint import (
    lint_paths,
    lint_source,
    resolve_severity,
)

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def _rules(src):
    return [f.rule for f in lint_source(textwrap.dedent(src))]


def test_r001_repeat_on_cache_flagged():
    assert "DS-R001" in _rules("""
        import jax.numpy as jnp
        def f(k_cache, G):
            return jnp.repeat(k_cache, G, axis=2)
    """)


def test_r001_method_form_flagged():
    """k_cache.repeat(G) is the same hazard as jnp.repeat(k_cache, G):
    the rule must scan the method receiver, not just args[0]."""
    assert "DS-R001" in _rules("""
        def f(k_cache, G):
            return k_cache.repeat(G, axis=2)
    """)


def test_r001_ignores_non_cache_repeat():
    assert "DS-R001" not in _rules("""
        import jax.numpy as jnp
        def f(logits, G):
            return jnp.repeat(logits, G, axis=0)
    """)


def test_r001_pragma_suppresses():
    assert "DS-R001" not in _rules("""
        import jax.numpy as jnp
        def f(k_cache, G):
            return jnp.repeat(k_cache, G, axis=2)  # lint: allow(DS-R001)
    """)


def test_r002_item_inside_jit():
    assert "DS-R002" in _rules("""
        import jax
        def step(params, batch):
            lr = params["lr"].item()
            return params
        step_fn = jax.jit(step)
    """)


def test_r002_float_on_traced_arg():
    assert "DS-R002" in _rules("""
        import jax
        @jax.jit
        def step(loss, x):
            return x * float(loss)
    """)


def test_r002_float_on_shape_ok():
    assert "DS-R002" not in _rules("""
        import jax
        @jax.jit
        def step(x):
            return x * float(x.shape[0])
    """)


def test_r002_nested_closure_inside_instrument():
    """Functions jitted via telemetry.instrument get the same scrutiny,
    including their nested closures."""
    assert "DS-R002" in _rules("""
        def build(telemetry):
            def fused(params, batch):
                def scaled(p):
                    return float(batch) * 2
                return scaled(params)
            return telemetry.instrument("fused", fused)
    """)


def test_r002_not_flagged_outside_jit():
    assert "DS-R002" not in _rules("""
        def host_logging(loss):
            return float(loss)
    """)


def test_r003_shape_branch_warns():
    findings = lint_source(textwrap.dedent("""
        import jax
        @jax.jit
        def step(x):
            if x.shape[0] > 4:
                return x * 2
            return x
    """))
    assert any(f.rule == "DS-R003" for f in findings)
    f = next(f for f in findings if f.rule == "DS-R003")
    assert resolve_severity(f) == "warn"  # warn-only rule, any path


def test_r004_missing_donation_on_buffer_args():
    findings = lint_source(textwrap.dedent("""
        import jax
        def step(master, opt_state, grad_acc):
            return master, opt_state, grad_acc
        jitted = jax.jit(step)
        donated = jax.jit(step, donate_argnums=(0, 1, 2))
    """))
    r004 = [f for f in findings if f.rule == "DS-R004"]
    assert len(r004) == 1  # only the undonated call site


def test_r005_host_transfers_in_serving_loop_flagged():
    """device_get / .item() / np.asarray-on-a-device-value inside a
    *Server step method are each one synchronous device round trip per round."""
    rules = _rules("""
        import numpy as np, jax
        class PagedServer:
            def _decode_step(self):
                out = np.asarray(self.pending_tokens)
                host = jax.device_get(self.lengths)
                n = self.count.item()
    """)
    assert rules.count("DS-R005") == 3


def test_r005_scoped_to_hot_loop_only():
    """Intake methods, non-scheduler classes, and literal-built arrays are
    host-side work, not device fetches — never flagged."""
    assert "DS-R005" not in _rules("""
        import numpy as np
        class PagedServer:
            def submit(self, prompt):
                return np.asarray(prompt)  # intake, not the step loop
            def _prefill_step(self):
                starts = np.asarray([0, 1], np.int32)  # literal: host array
        class PagePool:
            def _decode_step(self):
                return np.asarray(self.table)  # not a Server/Scheduler
        class CurriculumScheduler:
            def step(self, global_steps):
                # host-only training-side scheduler: no serving round
                # methods anywhere in the class, so step() is out of scope
                return np.asarray(self.schedule[global_steps])
    """)


def test_r005_pragma_suppresses_and_is_error_severity():
    findings = lint_source(textwrap.dedent("""
        import numpy as np
        class TokenScheduler:
            def _verify_round(self):
                a = np.asarray(self.out)
                b = np.asarray(self.out)  # lint: allow(DS-R005)
    """), path="deepspeed_tpu/foo.py")
    r005 = [f for f in findings if f.rule == "DS-R005"]
    assert len(r005) == 1  # the pragma'd line is suppressed
    assert resolve_severity(r005[0]) == "error"


def test_r005_warn_only_under_tests_prefix():
    f = lint_source(
        "import jax\n"
        "class FooServer:\n"
        "    def _decode_step(self):\n"
        "        return jax.device_get(self.x)\n",
        path="tests/unit/inference/fake.py",
    )[0]
    assert f.rule == "DS-R005"
    assert resolve_severity(f) == "warn"


def test_r006_blocking_gather_in_scan_body_flagged():
    """A hand-rolled param all-gather inside a lax.scan body is the gather
    the overlap pipeline (zero.prefetch_layers) should own."""
    assert "DS-R006" in _rules("""
        import jax
        def body(carry, per_layer):
            gathered = jax.lax.all_gather(per_layer, "data")
            return carry, gathered
        def stack(x, layers):
            return jax.lax.scan(body, x, layers)
    """)


def test_r006_psum_on_weights_flagged_and_activations_ok():
    src_w = """
        import jax
        def body(c, w_layer):
            full = jax.lax.psum(w_layer, "data")
            return c, full
        def run(x, ws):
            return jax.lax.scan(body, x, ws)
    """
    assert "DS-R006" in _rules(src_w)
    # activation collectives (sequence-parallel reductions on x / hidden)
    # are not the pipeline's gathers — out of scope
    assert "DS-R006" not in _rules("""
        import jax
        def body(c, x_chunk):
            h = jax.lax.psum(x_chunk, "sequence")
            return c, h
        def run(x, xs):
            return jax.lax.scan(body, x, xs)
    """)


def test_r006_outside_scan_body_not_flagged():
    assert "DS-R006" not in _rules("""
        import jax
        def gather(per_layer):
            return jax.lax.all_gather(per_layer, "data")
    """)


def test_r006_pragma_suppresses():
    assert "DS-R006" not in _rules("""
        import jax
        def body(carry, per_layer):
            g = jax.lax.all_gather(per_layer, "data")  # lint: allow(DS-R006)
            return carry, g
        def stack(x, layers):
            return jax.lax.scan(body, x, layers)
    """)


def test_r007_pool_internal_writes_flagged():
    """Direct mutation of PagePool state outside the pool — table writes,
    free-list surgery, refcount pokes, index edits, cache rebinds — each
    bypasses the CoW/refcount write barrier."""
    rules = _rules("""
        import numpy as np
        class Scheduler:
            def step(self, pool, slot, page):
                pool.page_table[slot, 0] = page
                pool.seq_lens[slot] = 4
                pool._free.append(page)
                pool._refcount[page] += 1
                pool._hash_index.clear()
                self.pool.cache = None
    """)
    assert rules.count("DS-R007") == 6


def test_r007_quiet_inside_pool_and_on_reads():
    """The pool's own methods are the sanctioned writers; reads and
    non-pool receivers with generic attr names stay out of scope."""
    assert "DS-R007" not in _rules("""
        import numpy as np
        class PagePool:
            def free_slot(self, slot):
                self.page_table[slot, :] = -1
                self.seq_lens[slot] = 0
                self._free.append(3)
                self._refcount[3] -= 1
        class SubPool(PagePool):
            def reset(self):
                self._hash_index.clear()
        def reader(pool, slot):
            return pool.page_table[slot], pool.seq_lens[slot]
        class Engine:
            def warm(self):
                self.cache = {}         # generic attr, non-pool receiver
                self._free = [1, 2]     # ditto
    """)


def test_r005_tp_ragged_step_host_transfer_flagged():
    """ISSUE 13 red test: the tensor-parallel scheduler path — ragged
    steps, their enqueue and their settle methods — is inside the
    one-fetch-per-dispatch budget too. A host transfer smuggled into a
    ``_tp_step`` / ``_ragged_step`` / ``_dispatch`` /
    ``_settle_fetched_rows`` costs a synchronous RTT on EVERY chip of the
    serving mesh, so DS-R005 must see those methods."""
    rules = _rules("""
        import numpy as np, jax
        class ShardedPagedServer:
            def _ragged_step(self):
                toks = np.asarray(self.pending)      # fetch per dispatch
            def _tp_step(self):
                lens = jax.device_get(self.lengths)  # ditto, tp spelling
            def _dispatch(self, packed):
                n = self.emitted.item()
            def _settle_fetched_rows(self, step, out):
                out = np.asarray(out)
    """)
    assert rules.count("DS-R005") == 4


def test_r005_tp_settle_pragma_budget_still_honored():
    """The sanctioned single packed fetch of a step stays pragma-able —
    the rule polices UNBUDGETED transfers, not the contract fetch."""
    findings = lint_source(textwrap.dedent("""
        import numpy as np
        class ShardedPagedServer:
            def _ragged_step(self):
                pass
            def _settle_ragged_rows(self, rows, out):
                out = np.asarray(out)  # lint: allow(DS-R005)
                extra = np.asarray(self.lengths)
    """), path="deepspeed_tpu/foo.py")
    r005 = [f for f in findings if f.rule == "DS-R005"]
    assert len(r005) == 1  # only the unbudgeted second fetch


def test_r007_kv_sharding_write_flagged():
    """ISSUE 13 red test: the pool's kv-head sharding is part of its
    device-layout invariants — rebinding it outside the pool (e.g. a TP
    helper 'fixing up' placement mid-serve) silently de-aliases every
    donated page buffer. DS-R007 must flag the write on any receiver."""
    rules = _rules("""
        class TPScheduler:
            def rebalance(self, pool, sharding):
                pool.kv_sharding = sharding
                self.server.pool.kv_sharding = None
    """)
    assert rules.count("DS-R007") == 2


def test_r007_kv_sharding_quiet_inside_pool():
    assert "DS-R007" not in _rules("""
        class PagePool:
            def __init__(self, kv_sharding=None):
                self.kv_sharding = kv_sharding
    """)


def test_r007_pragma_suppresses_and_is_error_severity():
    findings = lint_source(textwrap.dedent("""
        def restore(pool, table):
            pool.page_table[:] = table  # lint: allow(DS-R007)
            pool.seq_lens[:] = 0
    """), path="deepspeed_tpu/foo.py")
    r007 = [f for f in findings if f.rule == "DS-R007"]
    assert len(r007) == 1  # the pragma'd line is suppressed
    assert resolve_severity(r007[0]) == "error"


def test_r008_nonatomic_write_in_checkpoint_file_flagged():
    src = """
        def persist(path, data):
            with open(path, "wb") as f:
                f.write(data)
    """
    findings = lint_source(
        textwrap.dedent(src),
        path="deepspeed_tpu/runtime/checkpoint_engine/foo_engine.py",
    )
    assert [f.rule for f in findings] == ["DS-R008"]
    # same code in an unrelated file: out of scope
    assert not lint_source(textwrap.dedent(src), path="deepspeed_tpu/ops/foo.py")


def test_r008_checkpoint_function_flagged_in_any_file():
    src = """
        import os
        def save_checkpoint(save_dir, tag):
            with open(os.path.join(save_dir, "latest"), "w") as f:
                f.write(tag)
    """
    rules = [
        f.rule
        for f in lint_source(textwrap.dedent(src), path="deepspeed_tpu/runtime/engine.py")
    ]
    assert "DS-R008" in rules


def test_r008_sanctioned_patterns_quiet():
    """temp+rename staging, append-only logs, and reads are the sanctioned
    idioms — none may flag."""
    src = """
        import os
        def save_checkpoint(path, data, tag):
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:          # staged: the atomic pattern
                f.write(data)
            os.replace(tmp, path)
            with open(path + ".journal", "ab") as f:  # append-only journal
                f.write(data)
            with open(path, "rb") as f:          # read
                return f.read()
    """
    findings = lint_source(
        textwrap.dedent(src), path="deepspeed_tpu/runtime/checkpoint_engine/x.py"
    )
    assert "DS-R008" not in [f.rule for f in findings]


def test_r008_pragma_suppresses_and_is_error_severity():
    src = """
        def write_journal(path, tag):
            with open(path, "w") as f:  # lint: allow(DS-R008)
                f.write(tag)
    """
    assert "DS-R008" not in [
        f.rule for f in lint_source(textwrap.dedent(src), path="deepspeed_tpu/inference/journal.py")
    ]
    bad = textwrap.dedent(src).replace("  # lint: allow(DS-R008)", "")
    findings = lint_source(bad, path="deepspeed_tpu/inference/journal.py")
    assert [f.rule for f in findings] == ["DS-R008"]
    assert resolve_severity(findings[0]) == "error"


def test_r008_bench_record_paths_in_scope():
    src = """
        import json
        def _save_store(store, path):
            with open(path, "w") as f:
                json.dump(store, f)
    """
    assert "DS-R008" in [
        f.rule for f in lint_source(textwrap.dedent(src), path="bench.py")
    ]


def test_r009_raw_clock_in_step_loop_flagged():
    """A raw perf_counter (or time.time / device_sync) inside a step-loop
    method of an Engine/Server/Scheduler class forks a second timeline
    next to the unified tracer — red."""
    findings = _rules("""
        import time
        class FooServer:
            def step(self):
                t0 = time.perf_counter()
                return t0
        class BarEngine:
            def train_batch(self):
                return time.time()
        class BazScheduler:
            def _ragged_step(self):
                device_sync()
    """)
    assert findings.count("DS-R009") == 3


def test_r009_quiet_outside_scope():
    """Out of scope: non-step methods, non-engine classes, injected clocks,
    and the tracer/timer modules themselves (path exemption)."""
    assert "DS-R009" not in _rules("""
        import time
        class FooServer:
            def __init__(self, clock=None):
                self.clock = clock or time.perf_counter  # reference, not a call
            def save_checkpoint(self):
                return time.perf_counter()  # not a step-loop method
            def step(self):
                return self.clock()  # injected clock is the sanctioned idiom
        class Helper:
            def step(self):
                return time.perf_counter()  # not an Engine/Server/Scheduler
    """)
    src = """
        import time
        class FooServer:
            def step(self):
                return time.perf_counter()
    """
    import textwrap as _tw

    assert [
        f.rule for f in lint_source(_tw.dedent(src), path="deepspeed_tpu/utils/timer.py")
    ] == []
    assert [
        f.rule for f in lint_source(_tw.dedent(src), path="deepspeed_tpu/profiling/tracer.py")
    ] == []
    assert "DS-R009" in [
        f.rule for f in lint_source(_tw.dedent(src), path="deepspeed_tpu/inference/scheduler.py")
    ]


def test_r009_loader_next_in_scope():
    """An input-pipeline Loader's ``__next__`` runs once per microbatch on
    the step's critical path — a raw clock there is the same fork of the
    timeline as one in an engine step method. Red."""
    findings = _rules("""
        import time
        class FooLoader:
            def __next__(self):
                t = time.perf_counter()
                return time.time()
    """)
    assert findings.count("DS-R009") == 2


def test_r009_loader_quiet_outside_hot_methods():
    """A Loader's non-pipeline methods (state_dict etc.) may time freely,
    and the REAL dataloader module lints clean under the extended scope."""
    assert "DS-R009" not in _rules("""
        import time
        class RepeatingLoader:
            def state_dict(self):
                return {"t": time.time()}  # not a hot-path method
        class DataLoader:
            def __len__(self):
                return int(time.perf_counter())
    """)
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    path = os.path.join(root, "deepspeed_tpu", "runtime", "dataloader.py")
    with open(path) as fh:
        src = fh.read()
    assert [
        f.rule for f in lint_source(src, path="deepspeed_tpu/runtime/dataloader.py")
    ] == []


def test_r009_pragma_suppresses_and_is_error_severity():
    src = """
        import time
        class FooServer:
            def step(self):
                return time.perf_counter()  # lint: allow(DS-R009)
    """
    assert "DS-R009" not in _rules(src)
    f = lint_source(
        textwrap.dedent(src.replace("  # lint: allow(DS-R009)", "")),
        path="deepspeed_tpu/x.py",
    )[0]
    assert f.rule == "DS-R009"
    assert resolve_severity(f) == "error"


def test_r009_streamer_stream_family_in_scope():
    """ISSUE 16 extension: the host-offload *Streamer bucket methods run
    between every hot dispatch — a raw clock there forks the timeline
    exactly like one in an engine step method. Red."""
    findings = _rules("""
        import time
        class HostOffloadStreamer:
            def h2d_bucket(self, bi):
                t0 = time.perf_counter()
            def d2h_bucket(self, bi, m, ea, eas):
                return time.time()
            def materialize_writes(self, keep=0):
                time.monotonic()
            def drain_writes(self):
                device_sync()
        class FooEngine:
            def _take_streamed_offload_step(self, lr):
                return time.perf_counter()
    """)
    assert findings.count("DS-R009") == 5


def test_r009_streamer_unsanctioned_host_copy_flagged():
    """Stream-copy discipline: a raw device_put / device_get /
    copy_to_host_async / block_until_ready anywhere in a *Streamer
    OUTSIDE the sanctioned helpers bypasses the stream accounting the
    overlap gate audits. Red on each copy primitive."""
    findings = _rules("""
        import jax
        class HostOffloadStreamer:
            def take_staged(self, bi):
                return jax.device_put(self._exp_avg[0], s)
            def stream_stats(self):
                return jax.device_get(self._pending[0][1])
            def state_dict(self):
                arr.copy_to_host_async()
            def note_step(self):
                x.block_until_ready()
    """)
    assert findings.count("DS-R009") == 4


def test_r009_streamer_sanctioned_helpers_quiet():
    """The sanctioned stream helpers OWN the raw copies (that is the
    point of the rule); __init__ seeds host buffers before stepping and
    set_master_leaves is checkpoint-restore surgery. All green — and the
    real streamer module holds the contract."""
    assert "DS-R009" not in _rules("""
        import jax
        import numpy as np
        class HostOffloadStreamer:
            def __init__(self, tree):
                self._master = [np.array(jax.device_get(l), copy=True) for l in tree]
            def h2d_bucket(self, bi):
                return [jax.device_put(m, s) for m in self._exp_avg]
            def d2h_bucket(self, bi, m, ea, eas):
                m[0].copy_to_host_async()
            def _land(self, bufs, i, arr):
                np.copyto(bufs[i], np.asarray(jax.device_get(arr)))
            def drain_writes(self):
                arr.block_until_ready()
            def set_master_leaves(self, leaves):
                np.copyto(self._master[0], np.asarray(jax.device_get(leaves[0])))
        class BucketPlanner:
            def take_staged(self):
                return jax.device_put(x, s)  # only *Streamer classes are in scope
    """)
    path = os.path.join(REPO, "deepspeed_tpu", "runtime", "zero", "host_offload.py")
    findings = lint_paths([path])
    assert [f.rule for f in findings] == [], [f.render() for f in findings]


def test_r008_host_offload_is_a_persistence_path():
    """host_offload.py persists state checkpoints later trust — a raw
    open('w') there is in DS-R008 scope by path."""
    src = """
        def dump(path, payload):
            with open(path, "w") as fh:
                fh.write(payload)
    """
    hits = [
        f.rule
        for f in lint_source(textwrap.dedent(src), path="deepspeed_tpu/runtime/zero/host_offload.py")
    ]
    assert hits == ["DS-R008"]


def test_r010_jax_import_in_host_only_module_flagged():
    """The fleet router and the tracer are declared pure host code: any
    jax import form trips the rule there — and only there."""
    for src in (
        "import jax\n",
        "import jax.numpy as jnp\n",
        "from jax import numpy\n",
        "from jax.sharding import NamedSharding\n",
    ):
        hits = [
            f.rule
            for f in lint_source(src, path="deepspeed_tpu/inference/fleet.py")
        ]
        assert hits == ["DS-R010"], (src, hits)
    assert "DS-R010" in [
        f.rule
        for f in lint_source("import jax\n", path="deepspeed_tpu/profiling/tracer.py")
    ]


def test_r010_quiet_elsewhere_and_on_host_imports():
    # jax imports are the norm everywhere else in the library
    assert not lint_source(
        "import jax\n", path="deepspeed_tpu/inference/scheduler.py"
    )
    # numpy / stdlib / journal imports in the host-only modules are fine
    assert not lint_source(
        "import numpy as np\nimport zlib\n"
        "from deepspeed_tpu.inference.journal import RequestJournal\n",
        path="deepspeed_tpu/inference/fleet.py",
    )
    # a deliberate (hypothetical) exception carries a pragma
    assert not lint_source(
        "import jax  # lint: allow(DS-R010)\n",
        path="deepspeed_tpu/inference/fleet.py",
    )


@pytest.mark.parametrize("module", ["inference/fleet.py", "profiling/tracer.py"])
def test_r010_host_only_modules_actually_lint_clean(module):
    """The real router and tracer modules hold the contract (the gate's lint
    leg): the tracer reaches ``jax.profiler`` only through the sink the
    engines hand it."""
    path = os.path.join(REPO, "deepspeed_tpu", *module.split("/"))
    findings = lint_paths([path])
    assert [f.rule for f in findings] == [], [f.render() for f in findings]


def test_severity_tests_path_is_warn_only():
    f = lint_source("import jax.numpy as jnp\nx = jnp.repeat(k_cache, 2)\n", path="tests/unit/foo.py")[0]
    assert f.rule == "DS-R001"
    assert resolve_severity(f) == "warn"
    f2 = lint_source("import jax.numpy as jnp\nx = jnp.repeat(k_cache, 2)\n", path="deepspeed_tpu/foo.py")[0]
    assert resolve_severity(f2) == "error"


def test_library_lints_clean():
    """The gate itself: zero error-severity findings in deepspeed_tpu/
    (deliberate sites carry pragmas) — what tools/lint.sh enforces per
    commit."""
    findings = lint_paths([os.path.join(REPO, "deepspeed_tpu")])
    errors = [
        f.render()
        for f in findings
        if resolve_severity(f) == "error"
    ]
    assert not errors, "\n".join(errors)


def test_r011_device_put_onto_device_flagged():
    """The PR-12 incident shape: a pool-sized buffer device_put onto a
    bare device — the whole pool transiently commits to one chip."""
    assert "DS-R011" in _rules("""
        import jax, jax.numpy as jnp
        def place_pool(kv_pages):
            return jax.device_put(kv_pages, jax.devices()[0])
    """)


def test_r011_sharded_placement_ok():
    """Placing with a NamedSharding / spec tree is the sanctioned fix."""
    assert "DS-R011" not in _rules("""
        import jax
        def shard(params, shardings):
            return jax.device_put(params, shardings)
    """)
    assert "DS-R011" not in _rules("""
        import jax
        def shard(params, mesh, spec):
            from jax.sharding import NamedSharding
            return jax.device_put(params, NamedSharding(mesh, spec))
    """)


def test_r011_placementless_only_on_mesh_path():
    """A bare device_put of a sized value only flags inside mesh/shard
    code — default-device placement of host data is fine elsewhere."""
    assert "DS-R011" in _rules("""
        import jax
        def build_on_mesh(cache, mesh):
            return jax.device_put(cache)
    """)
    assert "DS-R011" not in _rules("""
        import jax
        def stage(cache):
            return jax.device_put(cache)
    """)


def test_r011_unsized_values_ok():
    assert "DS-R011" not in _rules("""
        import jax
        def f(x, mesh):
            return jax.device_put(x, jax.devices()[0])
    """)


def test_r011_pragma_suppresses_and_is_error_severity():
    findings = lint_source(
        textwrap.dedent("""
        import jax
        def per_shard(master, dev):
            return jax.device_put(master, dev)  # lint: allow(DS-R011)
    """),
        path="deepspeed_tpu/foo.py",
    )
    assert "DS-R011" not in [f.rule for f in findings]
    bad = lint_source(
        textwrap.dedent("""
        import jax
        def per_shard(master, dev):
            return jax.device_put(master, dev)
    """),
        path="deepspeed_tpu/foo.py",
    )
    hit = [f for f in bad if f.rule == "DS-R011"]
    assert hit and resolve_severity(hit[0]) == "error"


def test_r012_module_constant_in_jit_flagged():
    rules = _rules("""
        import jax, numpy as np
        TABLE = np.arange(1024.0)
        @jax.jit
        def f(x):
            return x + TABLE
    """)
    assert "DS-R012" in rules


def test_r012_constant_passed_as_argument_ok():
    assert "DS-R012" not in _rules("""
        import jax, numpy as np
        TABLE = np.arange(1024.0)
        @jax.jit
        def f(x, table):
            return x + table
        def call(x):
            return f(x, TABLE)  # capture-free: rides the arg path
    """)


def test_r012_local_shadow_ok():
    assert "DS-R012" not in _rules("""
        import jax, numpy as np
        TABLE = np.arange(4.0)
        @jax.jit
        def f(x):
            TABLE = x * 2
            return x + TABLE
    """)


def test_r012_is_warn_only():
    f = [
        x
        for x in lint_source(
            textwrap.dedent("""
        import jax, numpy as np
        C = np.zeros(8)
        @jax.jit
        def f(x):
            return x + C
    """),
            path="deepspeed_tpu/foo.py",
        )
        if x.rule == "DS-R012"
    ]
    assert f and resolve_severity(f[0]) == "warn"


def test_cli_json_and_rule_filter(tmp_path, capsys):
    """--json emits machine-readable findings and --rule narrows to the
    named rule ids (the structured interface the CI gates assert on)."""
    import json

    from deepspeed_tpu.analysis.source_lint import main

    bad = tmp_path / "bad.py"
    bad.write_text(
        textwrap.dedent("""
        import jax, jax.numpy as jnp
        def place(kv_pages, k_cache, G):
            jnp.repeat(k_cache, G)
            return jax.device_put(kv_pages, jax.devices()[0])
    """)
    )
    rc = main([str(bad), "--json", "--rule", "DS-R011"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert [f["rule"] for f in out] == ["DS-R011"]
    rc = main([str(bad), "--json", "--rule", "DS-R001"])
    out = json.loads(capsys.readouterr().out)
    assert [f["rule"] for f in out] == ["DS-R001"]
    assert rc == 1


def test_cli_rule_filter_rejects_unknown(tmp_path):
    import pytest

    from deepspeed_tpu.analysis.source_lint import main

    with pytest.raises(SystemExit):
        main([str(tmp_path), "--rule", "DS-R999"])


def test_r005_moe_routing_host_sync_flagged():
    """ISSUE 20 extension: host transfers inside the routing methods of a
    *Gate / *MoE / *MoELayer class run inside every traced step — each is
    one synchronous RTT stalling the a2a overlap pipeline. Red."""
    rules = _rules("""
        import numpy as np, jax
        class TopKGate:
            def forward(self, logits):
                counts = jax.device_get(self.exp_counts)
                return counts
        class MoE:
            def apply(self, params, x):
                n = self.capacity.item()
                return n
        class ShardedMoELayer:
            def dispatch(self, tokens):
                return np.asarray(self.dispatch_mask)
    """)
    assert rules.count("DS-R005") == 3


def test_r009_moe_routing_raw_clock_flagged():
    """A raw clock around the gate/dispatch path forks a second timeline
    next to the tracer and serializes the dispatch a2a. Red."""
    rules = _rules("""
        import time
        class TopKGate:
            def gate(self, logits):
                t0 = time.perf_counter()
                return t0
        class PRMoELayer:
            def combine(self, expert_out):
                return time.time()
    """)
    assert rules.count("DS-R009") == 2


def test_moe_routing_scope_quiet_on_cold_methods():
    """Out of scope: init/partition methods of MoE classes (host-side
    setup, not the routing path), and config-ish classes whose names end
    MoE-ish but define no routing methods."""
    assert "DS-R005" not in _rules("""
        import numpy as np
        class MoE:
            def init(self, rng):
                return np.asarray(self.seed)  # setup, not routing
            def partition_rules(self):
                return np.asarray(self.rules)
        class DeepSpeedMoEConfig:
            def validate(self):
                return np.asarray(self.moe_experts)  # no routing methods
    """)
    assert "DS-R009" not in _rules("""
        import time
        class MoE:
            def init(self, rng):
                return time.perf_counter()  # setup may time freely
    """)


def test_moe_package_lints_clean_under_routing_scope():
    """The real moe/ package (gate + dispatch + a2a fast path) must lint
    clean under the extended routing-path scope — the hot path stays free
    of host syncs and raw clocks by construction."""
    findings = lint_paths([os.path.join(REPO, "deepspeed_tpu", "moe")])
    assert [f for f in findings if f.rule in ("DS-R005", "DS-R009")] == []


def test_the_scheduler_has_one_sanctioned_fetch_a_dispatch_and_none_before_it():
    """``inference/scheduler.py`` as it stands: one dispatch site and exactly
    one device-to-host read for it, carrying the pragma; and where a step is enqueued behind the one in flight
    (``_pack``'s span, ``_dispatch``'s span) nothing reads the device or
    settles a step: the settle of the step before follows the enqueue, and
    the wait for the device is the last thing ``step()`` does."""
    import ast
    import re

    path = os.path.join(REPO, "deepspeed_tpu", "inference", "scheduler.py")
    src = open(path).read()
    rel = "deepspeed_tpu/inference/scheduler.py"
    assert [f for f in lint_source(src, path=rel) if f.rule == "DS-R005"] == []
    bare = [f for f in lint_source(re.sub(r"# lint: allow\(DS-R005\).*", "", src), path=rel) if f.rule == "DS-R005"]
    # the one step's call (with the states and their slots, or with neither)
    assert src.count("= step_fn(") == 1 and src.count("_fn(") == 1
    assert [re.search(r"PagedServer\.(\w+)", f.message).group(1) for f in bare] == ["_settle_ragged_rows"]

    server = next(n for n in ast.walk(ast.parse(src)) if isinstance(n, ast.ClassDef) and n.name == "PagedServer")
    methods = {n.name: n for n in server.body if isinstance(n, ast.FunctionDef)}

    def span_of(node):
        for item in getattr(node, "items", []):
            call = item.context_expr
            if isinstance(call, ast.Call) and call.args and isinstance(call.args[0], ast.Constant):
                return call.args[0].value
        return None

    def spans(fn):
        return {span_of(n): n for n in ast.walk(fn) if isinstance(n, ast.With) and span_of(n)}

    def calls(node):
        return [(n.func.attr if isinstance(n.func, ast.Attribute) else getattr(n.func, "id", ""), n.lineno) for n in ast.walk(node) if isinstance(n, ast.Call)]

    reads = {"asarray", "array", "device_get", "item", "block_until_ready", "_drain", "_settle_ragged_rows", "_wait_ragged_rows", "settle"}
    pack = spans(methods["_pack"])["serve.pack"]
    assert calls(pack) and not [name for name, _ in calls(pack) if name in reads]
    # the one drain a pack may need (a reservation that would preempt) comes before its span opens
    assert [line for name, line in calls(methods["_pack"]) if name == "_drain"] and all(
        line < pack.lineno for name, line in calls(methods["_pack"]) if name in reads
    )
    dispatch, emit = spans(methods["_dispatch"])["serve.dispatch"], spans(methods["_dispatch"])["serve.emit"]
    assert calls(dispatch) and not [name for name, _ in calls(dispatch) if name in reads]
    assert dispatch.end_lineno < emit.lineno and "_settle_ragged_rows" in [name for name, _ in calls(emit)]
    # the jitted call alone is ``serve.enqueue``, inside the dispatch; what follows it there is this file's Python
    enqueue = spans(methods["_dispatch"])["serve.enqueue"]
    assert "step_fn" in [name for name, _ in calls(enqueue)] and "set_cache" not in [name for name, _ in calls(enqueue)]
    assert dispatch.lineno < enqueue.lineno and enqueue.end_lineno < dispatch.end_lineno
    assert [m for m, fn in methods.items() if "serve.enqueue" in spans(fn)] == ["_dispatch"]
    # step(): admit (and pack again only for a newcomer), enqueue, ..., pack the next step, wait last
    order = [name for name, _ in sorted(calls(methods["step"]), key=lambda c: c[1]) if name in ("_dispatch", "_admit", "_pack", "_wait_ragged_rows")]
    assert order[:3] == ["_admit", "_pack", "_dispatch"] and order[-2:] == ["_pack", "_wait_ragged_rows"]


# --- the hand-kept method patterns, held against the scheduler as it stands ----
def _language(pattern):
    """Every string a finite pattern (literals, groups, alternatives, ``?``) matches."""
    from re import _parser as sre

    def expand(items):
        outs = [""]
        for op, arg in items:
            if op is sre.LITERAL:
                tails = [chr(arg)]
            elif op is sre.SUBPATTERN:
                tails = expand(arg[3])
            elif op is sre.BRANCH:
                tails = [t for alt in arg[1] for t in expand(alt)]
            elif op is sre.MAX_REPEAT and arg[:2] == (0, 1):
                tails = [""] + expand(arg[2])
            else:
                assert op is sre.AT, op  # ^ and $
                continue
            outs = [o + t for o in outs for t in tails]
        return outs

    return set(expand(sre.parse(pattern)))


def _paged_server_methods():
    import ast

    src = open(os.path.join(REPO, "deepspeed_tpu", "inference", "scheduler.py")).read()
    server = next(n for n in ast.walk(ast.parse(src)) if isinstance(n, ast.ClassDef) and n.name == "PagedServer")
    return src.splitlines(), {n.name: n for n in server.body if isinstance(n, ast.FunctionDef)}


def test_every_alternative_of_the_serving_patterns_names_a_method_the_scheduler_has():
    """``_SERVING_FN`` / ``_HOT_FN`` are kept by hand. Beside the round
    methods by their generic names (``_ROUND_FN``: any ``*Server`` /
    ``*Scheduler`` class, and this file's fixtures) every name they spell is
    a method ``PagedServer`` has today, so one that goes takes its
    alternative with it (the window's went with PR 61)."""
    import re

    from deepspeed_tpu.analysis import source_lint as sl

    _, methods = _paged_server_methods()
    generic = re.compile(rf"^_?{sl._ROUND_FN}$")
    for pattern in (sl._SERVING_FN, sl._HOT_FN):
        names = {n.lstrip("_") for n in _language(pattern.pattern) if not generic.match(n)}
        assert names and all(pattern.match(n) and pattern.match("_" + n) for n in names)
        assert {n for n in names if n not in methods and "_" + n not in methods} == set(), pattern.pattern
    assert not [m for m in methods if generic.match(m)]  # the scheduler has no method by a generic name
    assert "window" not in sl._HOT_FN.pattern + sl._SERVING_FN.pattern + sl._R009_FN.pattern and "plain_" not in sl._R009_FN.pattern
    # and the class qualifies as a serving loop at all
    assert sl._HOT_CLASS.search("PagedServer") and any(sl._SERVING_FN.match(m) for m in methods)


def test_every_scheduler_method_that_enqueues_a_program_or_reads_its_result_is_in_scope():
    """The other direction: a ``PagedServer`` method that calls a jitted
    program (the step's, the token feed's two), waits for the device or
    holds the sanctioned fetch is one DS-R005 looks at."""
    import ast

    from deepspeed_tpu.analysis.source_lint import _HOT_FN

    lines, methods = _paged_server_methods()
    jitted = {"step_fn", "_feed_tokens", "_next_tokens"}
    touching = set()
    for name, fn in methods.items():
        called = {n.func.attr if isinstance(n.func, ast.Attribute) else getattr(n.func, "id", "") for n in ast.walk(fn) if isinstance(n, ast.Call)}
        if called & (jitted | {"block_until_ready", "device_get"}) or any("allow(DS-R005)" in ln for ln in lines[fn.lineno - 1 : fn.end_lineno]):
            touching.add(name)
    assert {"_pack", "_dispatch", "_wait_ragged_rows", "_settle_ragged_rows"} <= touching
    assert [m for m in sorted(touching) if not _HOT_FN.match(m)] == []
    # what calls those is in scope too: the settle of the rows, and the loop itself
    assert all(_HOT_FN.match(m) for m in ("_settle_fetched_rows", "_settle_spec_row", "step", "run", "serve"))
