"""Hand-made serving scenes for the pairing tests: the scheduler's span tree of
a few calls of ``tick()`` and the device's executions, laid out by the rules
the program keeps (``inference/scheduler.py``, PR 43) so that every scene KNOWS
which execution each dispatch span enqueued and what the true shift is.

One device stream: a program starts ``LAUNCH`` after its dispatch mark or when
the one before it ends, whichever is later.  One host thread: a dispatch span
takes ``DISPATCH``; a ``tick_collect`` returns ``FETCH`` after its program
ended (at once where it ended long ago); a booking (``engine.*_emit``) follows
every dispatch, fetched or not.  Device stamps run ``shift`` EARLY: ``shift``
is what to add to them.
"""
from benchmark.xplane import HostEvent
from benchmark.xprograms import Execution, Programs, RawOp

LAUNCH, DISPATCH, FETCH, EMIT, SCHED = 0.0002, 0.0006, 0.0001, 0.0003, 0.0002
MODULE = {"prefill_pack": "jit_packed_ctx_impl", "decode_tick": "jit_decode_impl"}
BUILD = {"prefill_pack": "engine.pack_build", "decode_tick": "engine.decode_build"}
BOOKING = {"prefill_pack": "engine.pack_emit", "decode_tick": "engine.decode_emit"}
PARENT = {"prefill_pack": "sched.prefill", "decode_tick": "sched.decode"}
RECORDER = -50.0   # recorder clock minus trace clock


class Scene:
    """``enqueue`` and ``collect`` inside ``call`` write the spans a one-ahead
    ``tick()`` writes; ``back_to_back`` the old order's.  ``truth`` maps a
    dispatch span's id to its execution's ``run_id``."""

    def __init__(self, shift=0.0015, t0=100.0):
        self.shift, self.t, self.free = shift, t0, t0
        self.t0 = t0
        self.spans, self.runs, self.ops = [], [], []
        self.truth, self.waits = {}, []
        self.inflight, self._open, self._ids = [], [], 0

    # -- the span tree -------------------------------------------------------
    def _begin(self, name, **args):
        self._ids += 1
        args["span_id"] = self._ids
        if self._open:
            args["parent_id"] = self._open[-1][1]
        self._open.append((name, self._ids, self.t, args))
        return self._ids

    def _end(self):
        name, _, a, args = self._open.pop()
        self.spans.append((name, a, self.t, args))

    def _leaf(self, name, seconds, **args):
        i = self._begin(name, **args)
        self.t += seconds
        self._end()
        return i

    # -- the device ----------------------------------------------------------
    def _run(self, kind, seconds, dispatched_at):
        start = max(dispatched_at + LAUNCH, self.free)
        self.free = start + seconds
        run_id = len(self.runs)
        self.runs.append(Execution(MODULE[kind], run_id, start - self.shift, self.free - self.shift))
        # two ops with a bubble between them: the device's own idle time
        mid = start + seconds / 2
        self.ops += [RawOp(f"fusion.{run_id}", start - self.shift, mid - 1e-5 - self.shift),
                     RawOp(f"fusion.{run_id}", mid - self.shift, self.free - self.shift)]
        return run_id

    def aux(self, module, seconds=2e-5):
        """A small program beside the engine's own, enqueued now."""
        start = max(self.t + LAUNCH, self.free)
        self.free = start + seconds
        self.runs.append(Execution(module, len(self.runs), start - self.shift, self.free - self.shift))
        self.ops.append(RawOp(f"copy.{len(self.runs)}", start - self.shift, self.free - self.shift))

    # -- one ahead -----------------------------------------------------------
    def enqueue(self, programs, ahead):
        """One execution: ``programs`` of (kind, device seconds, fetched)."""
        ex = []
        for kind, seconds, fetched in programs:
            self._begin(PARENT[kind])
            self._leaf(BUILD[kind], SCHED, rows_ms=0.1)
            i = self._begin(kind, ahead=int(ahead), upload_ms=0.1,
                            dispatch_ms=1e3 * (DISPATCH - 1e-4), synced=False)
            self.t += DISPATCH
            self.truth[i] = self._run(kind, seconds, self.t - 1e-4)
            self._end()
            self._end()
            ex.append((kind, i, self.free, fetched))
        self.inflight.append(ex)

    def collect(self):
        for kind, i, done, fetched in self.inflight.pop(0):
            self._begin(PARENT[kind])
            if fetched:
                wait = max(done + FETCH - self.t, 2e-5)
                self._leaf("tick_collect", wait, what=kind)
                self.waits.append(wait)
            self._leaf(BOOKING[kind], EMIT)
            self._end()

    def call(self, programs=None, *, first=None, drain=False, host=0.0):
        """One ``tick()``: a drain collects everything first and enqueues
        ``programs`` with ``ahead`` 0; otherwise ``first`` (a call that found
        nothing enqueued) goes out with ``ahead`` 0, ``programs`` one ahead,
        and the oldest execution is collected.  ``host``: seconds of the
        scheduler's own work before any of it."""
        self._begin("sched.tick")
        self.t += SCHED + host
        if drain:
            self._begin("sched.drain", reason="test")
            while self.inflight:
                self.collect()
            self._end()
            if programs:
                self.enqueue(programs, ahead=False)
        else:
            if first:
                self.enqueue(first, ahead=False)
            if programs:
                self.enqueue(programs, ahead=True)
            if self.inflight:
                self.collect()
        self.t += SCHED
        self._end()
        self.t += SCHED   # the driver's loop between two calls

    def back_to_back(self, programs):
        """One ``tick()`` in the old order: each dispatch fetches inside its
        span (a pack that completes no prompt is closed unsynced and never
        fetched) and is booked at once."""
        self._begin("sched.tick")
        for kind, seconds, fetched in programs:
            self._begin(PARENT[kind])
            i = self._begin(kind, ahead=0, upload_ms=0.1, dispatch_ms=1e3 * (DISPATCH - 1e-4))
            self.t += DISPATCH
            self.truth[i] = self._run(kind, seconds, self.t - 1e-4)
            if fetched:
                self.t = max(self.t, self.free + FETCH)
            else:
                self._open[-1][3]["synced"] = False
            self._end()
            self._leaf(BOOKING[kind], EMIT)
            self._end()
        self._end()
        self.t += SCHED

    # -- what the readers are handed ------------------------------------------
    def programs(self, unmirrored=()):
        """(``Programs`` whose capture holds the whole scene, the recorder's
        spans on the recorder's clock)."""
        # a mirror carries what its span was OPENED with, as strings
        mirrors = {a["span_id"]: HostEvent(n, s, e, {k: str(a[k]) for k in (
                       "span_id", "ahead", "what", "reason") if k in a})
                   for n, s, e, a in self.spans if a["span_id"] not in unmirrored}
        runs = sorted(self.runs, key=lambda e: e.start)
        progs = Programs((self.t0 - 0.01, max(self.t, self.free) + 0.01), {0: runs},
                         {0: sorted(self.ops, key=lambda o: o.start)}, mirrors)
        spans = [(n, s + RECORDER, e + RECORDER, a) for n, s, e, a in self.spans]
        return progs, spans


STEP, PACK = 0.010, 0.012


def steady(calls=8):
    """The pipeline full from the first call on: decode steps only."""
    s = Scene()
    s.call([("decode_tick", STEP, True)], first=[("decode_tick", STEP, True)])
    for _ in range(calls):
        s.call([("decode_tick", STEP, True)])
    return s


def pack_and_step(calls=8):
    """Every execution a pack and a step; every third pack completes a prompt
    (the others are never fetched)."""
    s = Scene()
    ex = lambda k: [("prefill_pack", PACK, k % 3 == 0), ("decode_tick", STEP, True)]  # noqa: E731
    s.call(ex(1), first=ex(0))
    for k in range(calls):
        s.call(ex(k + 2))
    return s


def drained(calls=4):
    """A steady run, a drain (what is enqueued collected, the next execution
    out with ``ahead`` 0 and collected by the call after), a steady run."""
    s = steady(calls)
    s.call([("decode_tick", STEP, True)], drain=True)
    for _ in range(calls):
        s.call([("decode_tick", STEP, True)])
    return s


def interleaved(calls=9):
    """Pack-only, step-only and mixed executions by turns."""
    s = Scene()
    kinds = ([("prefill_pack", PACK, True)], [("decode_tick", STEP, True)],
             [("prefill_pack", PACK, False), ("decode_tick", STEP, True)])
    s.call(kinds[1], first=kinds[0])
    for k in range(calls):
        s.call(kinds[(k + 2) % 3])
    return s


def old_order(calls=6):
    """Back to back, as every tick ran until PR 43 and a drained tick still
    does: a pack that completes no prompt, then a step."""
    s = Scene()
    for k in range(calls):
        s.back_to_back([("prefill_pack", PACK, k % 2 == 0), ("decode_tick", STEP, True)])
    return s


def host_bound(calls=8):
    """The host sets the pace (8 ms of its own work a call for a step of
    6): a collect finds its program ended and the device idles between two
    steps."""
    s = Scene()
    step = [("decode_tick", 0.006, True)]
    s.call(step, first=step, host=0.008)
    for _ in range(calls):
        s.call(step, host=0.008)
    return s


SCENES = {"steady": steady, "pack_and_step": pack_and_step, "drained": drained,
          "interleaved": interleaved, "old_order": old_order, "host_bound": host_bound}
