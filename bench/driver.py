"""Wall-clock driver: the program's replicas, broker and router, paced by
the host clock.

It composes the same parts as ``repro.cluster.ClusterSim`` (HotMem
``ServeEngine`` replicas registered with one async ``HostMemoryBroker``,
arrivals routed by a tenant-pinning ``Router``), but time is the wall:

- each arrival is routed when it falls due, never earlier; in a closed
  loop, the request that follows a finished one falls due at the end of
  the tick that finished it;
- before every tick a replica's ``now`` is set to the wall seconds since
  the window opened, and its ``_recycle_idle`` is wrapped to set ``now``
  to the wall again first: ``_tick``'s idle branches jump ``now`` ahead
  (``+0.01``, ``+keep_alive/8``) just before recycling, and without the
  wrap those jumps would expire warm containers early;
- the broker keeps its default ``perf_counter`` clock, so reclaim orders
  carry wall stamps.

Busy replicas are ticked round-robin, as two VMs share one chip.  When no
tick did device work the driver sleeps until the next arrival, at most
``IDLE_POLL_S``, so keep-alive expiry is seen within that.  Route, each
replica's tick and the wait are host spans (``jax.profiler.TraceAnnotation``)
that the trace reduction attributes idle device time to.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional

import jax

from repro.serving.request import FunctionProfile, Request, State

IDLE_POLL_S = 0.002


@dataclasses.dataclass
class ReqRecord:
    req: Request
    replica: str
    due: float                        # engine time the request fell due
    in_window: bool                   # due inside the measured window
    emitted: int = 0                  # output tokens seen so far
    first: Optional[float] = None     # end of the tick of the first token
    last: Optional[float] = None      # end of the tick of the last token
    done: Optional[float] = None


@dataclasses.dataclass
class DecodeRecord:
    """One decode step of one replica, read as the step is called."""
    replica: str
    rows: int                         # arena rows decoded
    live: int                         # rows bound to a request
    bound: dict                       # row -> (rid, position)
    served: jax.Array                 # (rows,) greedy token per row


class Window:
    """What one paced run of the driver saw."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.open = 0.0               # engine time the window opened at
        self.t0 = 0.0                 # perf_counter at window open
        self.records: list[ReqRecord] = []
        self.itl: list[float] = []    # gaps whose both tokens fall in window
        self.tokens_in_window = 0
        self.event_span: dict[str, tuple[int, int]] = {}
        self.decode_span: tuple[int, int] = (0, 0)
        self.backlog: list[tuple[float, int]] = []
        self.ticks = 0
        self.end_wall: Optional[float] = None   # engine time at close


class Driver:
    """Drives engines (replica id -> ``ServeEngine``) through a schedule."""

    def __init__(self, engines: dict, replica_of: Callable,
                 clock: Callable[[], float] = time.perf_counter):
        from repro.cluster import Router
        self.engines = engines
        self.clock = clock
        self.router = Router(route_fn=lambda req, _engines: replica_of(req))
        self.t0 = clock()
        self.decodes: list[DecodeRecord] = []
        self.recording = False
        self._n = 0
        self._todos = {rid: deque() for rid in engines}
        self._live = {rid: [] for rid in engines}     # records in flight
        for eng in engines.values():
            self._pace(eng)
            self._record_decodes(eng)

    # ------------------------------------------------------------ hooks
    def wall(self) -> float:
        return self.clock() - self.t0

    def _pace(self, eng) -> None:
        """Hold the engine's time to the wall where ``_tick`` reads it
        after an idle jump (see module docstring)."""
        recycle = eng._recycle_idle

        def paced_recycle():
            eng.now = self.wall()
            recycle()
        eng._recycle_idle = paced_recycle

    def _record_decodes(self, eng) -> None:
        """Read every decode step the window drives: which rows were bound
        to which request at which position, and the greedy token of each
        row from the step's own logits."""
        driver = self
        vocab = eng.cfg.vocab_size

        class Steps(dict):
            def __setitem__(self, rows, fn):
                super().__setitem__(rows, driver._wrap_step(eng, fn, vocab))

        steps = Steps()
        for rows, fn in eng._decode_jit.items():
            steps[rows] = fn
        eng._decode_jit = steps

    def _wrap_step(self, eng, fn, vocab: int):
        def step(params, toks, pos, caches):
            logits, new = fn(params, toks, pos, caches)
            if self.recording:
                self.decodes.append(DecodeRecord(
                    eng.replica_id, int(toks.shape[0]), len(eng._row_req),
                    {row: (req.rid, req.position)
                     for row, req in eng._row_req.items()},
                    greedy(logits, vocab)))
            return logits, new
        return step

    # -------------------------------------------------------------- run
    def _busy(self, rid: str, todo: deque) -> bool:
        e = self.engines[rid]
        return bool(todo or e.pending or e.active or any(e.warm.values())
                    or e.host_work())

    def run(self, arrivals, seconds: float, *, profiles: dict,
            grace: float = 0.0, record: bool = False,
            sample_backlog_s: float = 0.5,
            on_close: Optional[Callable[[], None]] = None) -> Window:
        """Serve ``arrivals`` for ``seconds``: open-loop ``loadgen.Arrival``
        due ``a.t`` seconds after the window opens, or a
        ``loadgen.ClosedLoop`` whose clients issue requests until the
        window closes.  Then keep ticking without new arrivals until every
        request in flight is done or ``grace`` has passed.  Engine time
        runs on from earlier runs; requests still in flight from them are
        served, their tokens inside the window count and, in a closed
        loop, they are among the clients in flight, but they are not this
        window's records.  ``on_close`` is called once, when the window
        closes, and the grace runs from its end (stopping a trace takes
        seconds)."""
        win = Window(seconds)
        todos, live = self._todos, self._live
        clients = getattr(arrivals, "clients", None)
        sched = deque() if clients else deque(arrivals)
        self.decodes.clear()
        self.recording = record
        win.open = self.wall()
        win.t0 = self.t0 + win.open
        close = win.open + seconds
        for rid, e in self.engines.items():
            win.event_span[rid] = (len(e.events), len(e.events))
        next_sample = win.open
        grace_from = close            # grace runs from the end of on_close
        with jax.profiler.TraceAnnotation("window"):
            if clients:
                with jax.profiler.TraceAnnotation("route"):
                    for ten, n in clients.items():
                        busy = sum(rec.req.tenant == ten
                                   for recs in live.values() for rec in recs)
                        for _ in range(n - busy):
                            self._issue(arrivals.next(ten), win.open, win,
                                        close, profiles)
            while True:
                now = self.wall()
                if sched and win.open + sched[0].t <= now:
                    with jax.profiler.TraceAnnotation("route"):
                        while sched and win.open + sched[0].t <= now:
                            a = sched.popleft()
                            self._issue(a, win.open + a.t, win, close,
                                        profiles)
                if now >= close and win.end_wall is None:
                    win.end_wall = now
                    win.decode_span = (0, len(self.decodes))
                    for rid, e in self.engines.items():
                        win.event_span[rid] = (win.event_span[rid][0],
                                               len(e.events))
                    if on_close is not None:
                        on_close()
                        grace_from = max(close, self.wall())
                if now >= close and not sched and (
                        now >= grace_from + grace
                        or not any(live.values())):
                    break
                if now >= next_sample and now < close:
                    win.backlog.append(
                        (now - win.open, sum(len(e.pending) for e in
                                             self.engines.values())))
                    next_sample += sample_backlog_s
                worked = False
                for rid, e in self.engines.items():
                    if not self._busy(rid, todos[rid]):
                        continue
                    before = len(e.events)
                    e.now = self.wall()
                    with jax.profiler.TraceAnnotation(f"tick.{rid}"):
                        e._tick(todos[rid])
                    win.ticks += 1
                    worked |= len(e.events) > before
                    t = self.wall()
                    done = self._tokens(win, live[rid], t, close)
                    if clients and t < close and done:
                        with jax.profiler.TraceAnnotation("route"):
                            for rec in done:
                                self._issue(arrivals.next(rec.req.tenant),
                                            t, win, close, profiles)
                if not worked:
                    due = win.open + sched[0].t if sched \
                        else now + IDLE_POLL_S
                    pause = min(due - self.wall(), IDLE_POLL_S)
                    if pause > 0:
                        with jax.profiler.TraceAnnotation("wait"):
                            time.sleep(pause)
        if win.end_wall is None:
            win.end_wall = self.wall()
            win.decode_span = (0, len(self.decodes))
        self.recording = False
        return win

    def _issue(self, a, due: float, win: Window, close: float,
               profiles: dict) -> None:
        """Route one request, due at engine time ``due``, and record it."""
        req = Request(rid=f"{a.tenant}{self._n}", profile=profiles[a.function],
                      submit_s=due, tenant=a.tenant)
        self._n += 1
        rid = self.router.route(req, self.engines,
                                {r: len(q) for r, q in self._todos.items()})
        self._todos[rid].append(req)
        rec = ReqRecord(req, rid, due, due < close)
        win.records.append(rec)
        self._live[rid].append(rec)

    def _tokens(self, win: Window, live: list, t: float,
                close: float) -> list:
        """Token times: the engine emits at most one token per request per
        tick, so the end of the tick is the token's time.  Returns the
        records that finished in this tick."""
        keep, done = [], []
        for rec in live:
            req = rec.req
            emitted = max(0, req.position - req.profile.prompt_tokens) \
                if req.state is not State.PENDING else 0
            if emitted > rec.emitted:
                if rec.first is None:
                    rec.first = t
                elif t <= close and rec.last is not None \
                        and rec.last >= win.open:
                    win.itl.append(t - rec.last)
                if t <= close:
                    win.tokens_in_window += emitted - rec.emitted
                rec.emitted = emitted
                rec.last = t
            if req.state in (State.DONE, State.KILLED):
                rec.done = t
                done.append(rec)
            else:
                keep.append(rec)
        live[:] = keep
        return done


@jax.jit
def _greedy(logits, mask):
    return jax.numpy.argmax(jax.numpy.where(mask, logits, -jax.numpy.inf),
                            axis=-1).astype(jax.numpy.int32)


_MASKS: dict = {}


def greedy(logits, vocab: int):
    """Greedy token of each row over the served vocabulary (padded vocab
    entries are never served)."""
    vp = logits.shape[-1]
    if vp not in _MASKS:
        _MASKS[vp] = jax.numpy.arange(vp) < vocab
    return _greedy(logits, _MASKS[vp])


def make_profiles(mix: dict, partition_tokens: int) -> dict:
    """One ``FunctionProfile`` per function of the mix, shared by all of
    its invocations (the engine keys warm containers by profile name)."""
    out = {}
    for ten in mix["tenants"]:
        for f in ten["functions"]:
            out[f["name"]] = FunctionProfile(
                f["name"], prompt_tokens=int(f["prompt"]),
                decode_tokens=int(f["decode"]),
                max_tokens=partition_tokens, tenant=ten["name"])
    return out
