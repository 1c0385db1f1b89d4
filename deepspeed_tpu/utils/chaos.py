"""Deterministic fault-injection harness for crash-restart testing.

Production TPU jobs die at arbitrary instants — slices are preempted, hosts
OOM, disks fill mid-write. The fault-tolerance guarantees this repo makes
(``latest`` never resolves to a torn checkpoint; ``auto_resume`` losses are
bit-identical; serving streams resume byte-identically from the journal) are
only guarantees if a kill at EVERY dangerous instant is actually exercised.
This module names those instants as **injection points** and arms them with
a seeded, fully deterministic schedule, so the crash-restart test matrix is
reproducible down to the byte.

Injection points (the canonical set — sites call ``chaos.point(NAME, ...)``):

* ``ckpt.pre_commit``      — checkpoint fully staged, rename not yet issued
* ``ckpt.mid_commit``      — re-save of an existing tag: the old checkpoint
  is moved aside and the new one not yet renamed in (the only instant the
  tag has no directory; recovery restores the moved-aside copy)
* ``ckpt.mid_array_write`` — between the array payload and the metadata
  write inside the staging dir (a half-written snapshot)
* ``ckpt.post_commit``     — directory renamed into place, ``latest`` marker
  not yet updated
* ``serve.mid_step``       — inside the serving scheduler step, after the
  device dispatch/emits but before the journal flush
* ``train.mid_step``       — a single optimizer step: the step program was
  dispatched and the engine adopted the donated state, but none of the
  host bookkeeping (counters, lr schedule, interval checkpoint) committed;
  a kill here must resume bit-identically from the last committed
  checkpoint — exercised on the expert-sharded MoE config, whose param
  tree spans two mesh axes
* ``journal.append``       — right after a journal record batch reaches the
  OS (the classic torn-tail instant; pair with the ``truncate`` action)
* ``fleet.replica_kill``   — at the top of one replica's turn inside the
  fleet router's step loop (``inference/fleet.py``): the replica is its
  own failure domain, so a ``raise`` here is ONE replica dying while the
  router and the rest of the fleet survive (the router catches the kill
  and re-routes the dead replica's live requests from its journal); the
  ``exit`` action still kills the whole process — the ``-m slow``
  restart-and-adopt case
* ``fleet.mid_migration``  — inside a live request migration, after the
  state left the source replica's memory but before the target durably
  re-seeded it (the double-claim/no-claim window the target-journal-first
  ordering and router-side dedup exist for)
* ``fleet.mid_drain``      — between two migrations of an elastic drain:
  the draining replica dies half-emptied and the remainder must re-route
  from its journal with zero acked tokens dropped

Actions:

* ``raise``    — raise :class:`ChaosKilled` (a ``BaseException`` subclass, so
  ordinary ``except Exception`` recovery code cannot swallow it — exactly
  like a real SIGKILL, nothing downstream of the point runs). In a
  background writer thread this kills the thread silently, leaving torn
  files behind — the in-process simulation of dying mid-write.
* ``exit``     — ``os._exit(137)``: a REAL abrupt death (no atexit, no
  flushing). For the subprocess-driven slow matrix.
* ``truncate`` — chop ``nbytes`` off the end of ``ctx["path"]`` (a torn
  append), then die via ``raise``.
* ``corrupt``  — overwrite the last ``nbytes`` of ``ctx["path"]`` with
  deterministic garbage (bitrot / partial overwrite), then die via
  ``raise``.

Usage::

    from deepspeed_tpu.utils import chaos
    chaos.install(chaos.ChaosSchedule([chaos.ChaosRule("ckpt.pre_commit")]))
    try:
        engine.save_checkpoint(d)      # dies at the armed instant
    except chaos.ChaosKilled:
        pass
    finally:
        chaos.uninstall()
    # ... build a fresh engine and auto_resume: the guarantees must hold.

The default state is DISARMED: ``chaos.point`` is a single ``is None`` check,
so production code paths pay nothing.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

# The canonical injection points. Sites may add new ones; tests iterate this
# list to build the crash matrix, so keep it in sync with the call sites.
POINTS = (
    "ckpt.pre_commit",
    "ckpt.mid_commit",  # re-save window: old checkpoint moved aside, new not yet in place
    "ckpt.mid_array_write",
    "ckpt.post_commit",
    "serve.mid_step",
    "train.mid_step",  # a single optimizer step: the step program dispatched
    # and the donated state adopted, but the counters / lr schedule / interval
    # checkpoint not yet committed — resume must replay from the last
    # committed checkpoint bit-identically (the MoE expert-sharded state
    # rides the same contract as the dense tree)
    "train.mid_offload_stream",  # ZeRO-Infinity streamed step, mid-bucket:
    # some host offload buffers updated, others not, the step uncommitted —
    # resume must rebuild the host state from the last checkpoint, never
    # trust the torn buffers

    "journal.append",
    "fleet.replica_kill",  # one replica's turn in the fleet step loop: raise =
    # that replica dies (router survives + re-routes), exit = whole process
    "fleet.mid_migration",  # state off the source, not yet durable on the target
    "fleet.mid_drain",  # a draining replica dies between two migrations
)

_ACTIONS = ("raise", "exit", "truncate", "corrupt")


class ChaosKilled(BaseException):
    """The simulated kill. Deliberately NOT an ``Exception``: recovery/retry
    code that catches ``Exception`` must not be able to 'survive' a kill —
    nothing after the injection point may run, same as SIGKILL."""


@dataclass
class ChaosRule:
    """Fire ``action`` on the ``hit``-th arrival at ``point`` (1-based)."""

    point: str
    hit: int = 1
    action: str = "raise"
    nbytes: int = 16  # tail bytes for truncate/corrupt
    fired: bool = field(default=False, init=False)

    def __post_init__(self):
        if self.action not in _ACTIONS:
            raise ValueError(f"unknown chaos action {self.action!r} (have {_ACTIONS})")
        if self.hit < 1:
            raise ValueError(f"hit is 1-based, got {self.hit}")


class ChaosSchedule:
    """An armed set of rules plus per-point arrival counters. Deterministic:
    the n-th arrival at a point always sees the same verdict."""

    def __init__(self, rules: Sequence[ChaosRule]):
        self.rules = list(rules)
        self.counts: Dict[str, int] = {}
        self.fired_log: List[str] = []  # "<point>#<hit>:<action>" per firing

    def fire(self, point: str, **ctx) -> None:
        n = self.counts.get(point, 0) + 1
        self.counts[point] = n
        for rule in self.rules:
            if rule.fired or rule.point != point or rule.hit != n:
                continue
            rule.fired = True
            self.fired_log.append(f"{point}#{n}:{rule.action}")
            self._act(rule, ctx)

    def _act(self, rule: ChaosRule, ctx: Dict) -> None:
        # pre-death hooks (the flight recorder): run BEFORE the action so a
        # postmortem dump exists even for the real os._exit, which skips
        # every atexit/finally downstream. Hook failures never save the
        # process — the kill proceeds regardless.
        for hook in list(_KILL_HOOKS):
            try:
                hook(rule.point, rule.action)
            except Exception:
                pass
        if rule.action == "exit":
            os._exit(137)  # the real thing: no atexit, no flushing
        if rule.action in ("truncate", "corrupt"):
            # file surgery applies only to file-backed points (journal
            # segments); on a directory-backed point (checkpoint staging)
            # the action degrades to the plain kill — it must never raise
            # an ordinary, swallowable IsADirectoryError instead
            path = ctx.get("path")
            if path and os.path.isfile(path):
                size = os.path.getsize(path)
                with open(path, "r+b") as f:
                    if rule.action == "truncate":
                        f.truncate(max(0, size - rule.nbytes))
                    else:
                        n = min(rule.nbytes, size)
                        f.seek(size - n)
                        # deterministic garbage: position-keyed, not random
                        f.write(bytes((0xA5 ^ (i & 0xFF)) for i in range(n)))
        raise ChaosKilled(f"chaos: killed at {rule.point} (hit {rule.hit})")


_SCHEDULE: Optional[ChaosSchedule] = None

# Pre-death hooks: callables ``(point, action) -> None`` run right before a
# rule's action executes (before the ChaosKilled raise AND before the real
# os._exit). The flight recorder (profiling/tracer.py) registers here so
# every injected kill leaves a postmortem file naming the armed point.
_KILL_HOOKS: List = []


def add_kill_hook(fn) -> None:
    if fn not in _KILL_HOOKS:
        _KILL_HOOKS.append(fn)


def remove_kill_hook(fn) -> None:
    if fn in _KILL_HOOKS:
        _KILL_HOOKS.remove(fn)


def install(schedule: ChaosSchedule) -> ChaosSchedule:
    """Arm a schedule (replacing any armed one) and return it."""
    global _SCHEDULE
    _SCHEDULE = schedule
    return schedule


def uninstall() -> None:
    global _SCHEDULE
    _SCHEDULE = None


def active() -> Optional[ChaosSchedule]:
    return _SCHEDULE


def point(name: str, **ctx) -> None:
    """An injection site. Free when disarmed (one None check)."""
    if _SCHEDULE is not None:
        _SCHEDULE.fire(name, **ctx)


def seeded_schedule(
    seed: int,
    points: Sequence[str] = POINTS,
    n_faults: int = 1,
    max_hit: int = 3,
    actions: Sequence[str] = ("raise",),
) -> ChaosSchedule:
    """A reproducible schedule: ``seed`` fully determines which points fire,
    on which arrival, with which action — the matrix tests sweep seeds
    instead of hand-writing every combination."""
    import numpy as np

    rs = np.random.RandomState(seed)
    rules = [
        ChaosRule(
            point=points[int(rs.randint(len(points)))],
            hit=int(rs.randint(1, max_hit + 1)),
            action=actions[int(rs.randint(len(actions)))],
        )
        for _ in range(n_faults)
    ]
    return ChaosSchedule(rules)
