"""Run one benchmark cell on the accelerator this process finds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` -> ``workloads``) names a configuration
(``bench/configs/<name>.json`` with its plain reference beside it) and a
traffic mix (``bench/traffic/<name>.json``).  Set-up makes the weights on the
device from the seed, builds the program's replicas under one broker,
compiles every shape the cell's traffic can reach and serves a warm-up of
that traffic.  The window then serves the mix by the wall clock, open or
closed loop as the mix says (``bench/driver.py``).  With ``--trace 0`` the last stdout line carries the
cell's end-to-end metrics; with ``--trace 1`` the window is profiled and the
line carries its per-layer metrics (``bench/metrics/<name>.py``).  After
the window the served tokens of a seeded sample of finished requests are
compared with the float32 reference, restored rows with their captured
blobs, and the broker's ledger with its invariants; each number compared is
printed with its limit (``bench/limits/<cell>.json``).

Two options serve the readings that set the cell's numbers, and no run of
the benchmark itself: ``--control 1`` judges the float8 reference in the
program's place, so ``correct`` comes out false (the limit's upper
reading; the program's own is printed beside it); ``--sweep r1,r2,...``
serves one window per offered rate after one set-up and prints each
rate's backlog (the knee search).

Off a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


class NoAccelerator(RuntimeError):
    pass


# --------------------------------------------------------------- loading
def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; cells: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, conf


def metric_reader(name: str):
    """``bench/metrics/<name>.py``, else the file of the name's stem (a
    metric split by cell, ``decode_step_ms.saturate``, shares its reader)."""
    for stem in (name, name.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            return load_module(path, f"bench_metric_{stem}").read
    raise FileNotFoundError(f"no reader bench/metrics/{name}.py")


def require_accelerator(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, but JAX found "
                            f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's persistent cache (``$JAX_COMPILATION_CACHE_DIR``, else
    a fixed directory in the checkout), keeping every program however short
    its compile, so a later run of the cell compiles nothing."""
    from repro.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles(logging.Handler):
    """Counts new programs (lowerings) and backend compiles by phase, and
    keeps the names of those compiled while ``naming`` is on."""

    def __init__(self):
        super().__init__()
        self.lowered = 0
        self.compiled = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" with ")[0][len("Compiling "):])

    def naming(self, on: bool) -> None:
        """Log compiles while on (JAX logs each with its name)."""
        jax.config.update("jax_log_compiles", on)
        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
            lg = logging.getLogger(name)
            if on:
                lg.addHandler(self)
                lg.propagate = False
            else:
                lg.removeHandler(self)
                lg.propagate = True

    def _on(self, name: str, _secs: float, **_kw) -> None:
        if name.endswith("jaxpr_to_mlir_module_duration"):
            self.lowered += 1
        elif name.endswith("backend_compile_duration"):
            self.compiled += 1

    def mark(self) -> tuple[int, int]:
        return self.lowered, self.compiled


_COMPILES: list = []


def compiles() -> Compiles:
    if not _COMPILES:
        _COMPILES.append(Compiles())
    return _COMPILES[0]


# ----------------------------------------------------------------- set-up
class Cell:
    """Everything one run of a cell holds."""

    def __init__(self, name: str, cj: dict, ref, mix: dict, seed: int):
        self.name, self.cj, self.ref, self.mix, self.seed = \
            name, cj, ref, mix, seed
        self.cfg = ref.program_config(cj)
        self.engines: dict = {}
        self.driver = None
        self.broker = None
        self.spec = None
        self.profiles: dict = {}
        self.params = None


def build(name: str, cj: dict, ref, mix: dict, seed: int) -> Cell:
    from repro.cluster import HostMemoryBroker
    from repro.core.arena import ArenaSpec
    from repro.models import model as M
    from repro.serving.engine import ServeEngine

    from bench import driver as D

    c = Cell(name, cj, ref, mix, seed)
    key = jax.random.key(seed % (2 ** 32))
    want = M.param_specs(c.cfg)
    vocab_rows = want["embed"]["tok"].shape[0]
    c.params = jax.jit(lambda k: ref.to_program(
        cj, ref.make_weights(cj, k), vocab_rows))(key)
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), c.params)
    exp = jax.tree.map(lambda s: (s.shape, str(np.dtype(s.dtype))), want,
                       is_leaf=lambda s: hasattr(s, "init"))
    if got != exp:
        raise ValueError("weights do not match the program's parameter "
                         "tree")
    T, n_part = cj["partition_tokens"], cj["n_partitions"]
    c.spec = ArenaSpec.from_model(c.cfg, partition_tokens=T,
                                  n_partitions=n_part,
                                  block_tokens=max(1, T // 16))
    bpp = c.spec.blocks_per_partition
    pool = mix["pool_partitions"]
    c.broker = HostMemoryBroker(
        budget_units=mix["budget_partitions"] * bpp, async_reclaim=True,
        snapshot_pool_units=pool * bpp if pool else None)
    for i, rid in enumerate(mix["replicas"]):
        c.engines[rid] = ServeEngine(
            c.cfg, c.params, c.spec, mode="hotmem",
            keep_alive=mix["keep_alive_s"], seed=i, broker=c.broker,
            replica_id=rid)
    replica_of = {t["name"]: t["replica"] for t in mix["tenants"]}
    c.driver = D.Driver(c.engines, lambda req: replica_of[req.tenant])
    c.profiles = D.make_profiles(mix, T)
    return c


def reachable_rows(c: Cell) -> list[int]:
    """Arena sizes the cell's traffic can reach: a shrink that finds only
    part of its suffix free, a partial grant, a drained order or a claimed
    fill stops at any size.  A replica alone never goes below its ladder;
    beside others, a drained order can take it down to one row and leave
    another the whole budget."""
    eng = next(iter(c.engines.values()))
    low = eng.ladder[0] if len(c.engines) == 1 else 1
    top = min(c.spec.n_partitions, c.mix["budget_partitions"])
    return list(range(low, top + 1))


def warm_shapes(c: Cell) -> None:
    """Compile, outside the window, every program the window can call:
    each replica's decode step at every reachable size, the greedy read of
    its logits, the snapshot capture and restore at every size (the
    engine's own dummy-execute, then marked done as the engine marks it),
    the cold-start row write, and the resize copies between neighbouring
    sizes and to each rung of the ladder."""
    from repro.models import model as M

    from bench import driver as D
    sizes = reachable_rows(c)
    ladder = next(iter(c.engines.values())).ladder
    T = c.spec.partition_tokens
    for eng in c.engines.values():
        for r in sizes:
            eng._warm_decode(r)
    logits = jax.eval_shape(
        lambda p, t, po, k: M.decode_step(c.cfg, p, t, po, k), c.params,
        jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
        M.abstract_caches(c.cfg, 2, T))[0]
    for r in sizes:
        D.greedy(jnp.zeros((r,) + logits.shape[1:], logits.dtype),
                 c.cfg.vocab_size)
    eng = next(iter(c.engines.values()))
    one = M.init_caches(c.cfg, 1, T)
    layout = M.cache_row_layout(one)
    blob = jnp.zeros((1, layout.total_elems), layout.dtype)
    idx = jnp.zeros((1,), jnp.int32)
    caches = M.init_caches(c.cfg, max(sizes), T)
    for r in sorted(sizes, reverse=True):
        if M.cache_num_rows(caches) != r:
            caches = M.cache_slice_rows(caches, r)  # the drain r+1 -> r
        ops = [lambda: M.cache_write_row(caches, one, 0)]
        if c.mix["pool_partitions"]:
            ops += [lambda: M.cache_read_rows(caches, idx, layout=layout,
                                              impl=eng.snapshot_impl),
                    lambda: M.cache_write_rows(caches, blob, idx,
                                               layout=layout,
                                               impl=eng.snapshot_impl),
                    lambda: _row_mismatch(caches, idx[0], blob,
                                          _row_axes(caches))]
            for e in c.engines.values():
                e._snap_warmed.update({("capture", r, e.snapshot_impl),
                                       ("restore", r, e.snapshot_impl)})
        for t in sizes:
            if t != r and (abs(t - r) == 1 or t in ladder):
                ops.append(lambda t=t: M.cache_grow_rows(caches, t)
                           if t > r else M.cache_slice_rows(caches, t))
        for op in ops:                  # one output alive at a time
            jax.block_until_ready(op())
    del caches


def _row_axes(caches) -> tuple:
    """Row axis of each cache leaf in flatten order: stacked layer groups
    hold rows on axis 1, unstacked tail blocks on axis 0."""
    return tuple(ax for key, sub in caches.items()
                 for ax in [0 if key == "tail" else 1] * len(
                     jax.tree.leaves(sub)))


@functools.partial(jax.jit, static_argnums=(3,))
def _row_mismatch(caches, row, blob, axes):
    """Elements of arena row ``row`` (every leaf's slice, flattened in
    leaf order) that differ from ``blob`` (1, row_elems)."""
    img = jnp.concatenate([
        jnp.take(x, row, axis=ax).reshape(-1)
        for x, ax in zip(jax.tree.leaves(caches), axes)])
    return jnp.sum(img != blob[0], dtype=jnp.int32)


def install_restore_probe(c: Cell) -> list:
    """Check every restore the window runs: read the written row back by
    plain indexing and count the elements that differ from the blob the
    restore was given (a device scalar, read after the window)."""
    from repro.models import model as M
    found: list = []
    write = M.cache_write_rows

    def probed(caches, blob, rows, *, layout=None, impl="pallas"):
        out = write(caches, blob, rows, layout=layout, impl=impl)
        found.append(_row_mismatch(out, rows[0], blob, _row_axes(out)))
        return out

    M.cache_write_rows = probed
    c.restore_probe_undo = lambda: setattr(M, "cache_write_rows", write)
    return found


# ----------------------------------------------------------------- window
def serve_warmup(c: Cell) -> None:
    from bench import loadgen
    sweep = loadgen.cold_sweep(c.mix)
    c.driver.run(sweep, 0.0, profiles=c.profiles, grace=120.0)
    warm = loadgen.generate(c.mix, c.seed, c.mix["warmup_s"],
                            stream="warmup")
    c.driver.run(warm, c.mix["warmup_s"], profiles=c.profiles, grace=0.0)


def serve_window(c: Cell, seconds: float, trace: bool,
                 arrivals=None):
    from bench import loadgen
    if arrivals is None:
        arrivals = loadgen.generate(c.mix, c.seed, seconds)
    if isinstance(arrivals, list) and arrivals:
        shift = arrivals[0].t
        arrivals = [dataclasses.replace(a, t=a.t - shift) for a in arrivals]
    marks = compiles().mark()
    compiles().names.clear()
    compiles().naming(True)
    tr = {}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))

    def closed():
        if trace and "stopped" not in tr:
            jax.profiler.stop_trace()
            tr["stopped"] = True
        tr["compiles"] = tuple(b - a for a, b in zip(marks,
                                                     compiles().mark()))
        compiles().naming(False)
        tr["names"] = list(compiles().names)

    win = c.driver.run(arrivals, seconds, profiles=c.profiles,
                       grace=c.mix["grace_s"], record=True, on_close=closed)
    if "compiles" not in tr:
        closed()
    win.compiles = tr["compiles"]
    win.compiled_names = tr["names"]
    return win


# ---------------------------------------------------------------- metrics
def p95(values: list) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]


def end_to_end(c: Cell, win) -> dict:
    close = win.open + win.seconds
    recs = [r for r in win.records if r.in_window]
    horizon = close + c.mix["grace_s"]
    ttft = [(r.first if r.first is not None else horizon) - r.due
            for r in recs]
    out = {"ttft_p95_ms": 1e3 * p95(ttft) if ttft else None,
           "itl_p95_ms": 1e3 * p95(win.itl) if win.itl else None,
           "output_tokens_per_s": win.tokens_in_window / win.seconds}
    bpu = c.spec.bytes_per_partition / c.spec.blocks_per_partition
    lo, hi = win.t0, win.t0 + win.seconds
    orders = [o for o in c.broker.orders.values()
              if o.closed_at is not None and lo <= o.closed_at <= hi]
    got = sum(o.filled for o in orders) * bpu / 2 ** 30
    if orders and got > 0:
        out["reclaim_ms_per_gib"] = 1e3 * sum(
            o.closed_at - o.issued_at for o in orders) / got
    return out


class RunView:
    """What a per-layer reader may read: the window's step events and
    decode records, the trace summary, the configuration and the peaks."""

    def __init__(self, c: Cell, win, summary, peak: dict):
        self.cell, self.win, self.trace, self.peak = c, win, summary, peak
        self.cj, self.ref = c.cj, c.ref
        self.seconds = win.seconds
        self.events = []                   # (replica, StepEvent) in window
        for rid, e in c.engines.items():
            a, b = win.event_span[rid]
            self.events += [(rid, ev) for ev in e.events[a:b]]
        a, b = win.decode_span
        self.decodes = c.driver.decodes[a:b]
        self.prompt_of = {r.req.rid: r.req.profile.prompt_tokens
                          for r in win.records}
        self.row_bytes = c.ref.row_bytes(c.cj, c.spec.partition_tokens)

    def step_flops(self) -> float:
        """Model FLOPs of the window: every prompt token of every prefill
        and every live output token, each attending its own context."""
        cj, ref = self.cj, self.ref
        head = ref.head_flops(cj)
        total = 0.0
        a = ref.token_flops(cj, 0)
        b = ref.token_flops(cj, 1) - a          # per position attended
        for _rid, ev in self.events:
            if ev.kind == "prefill":
                n = self.prompt_of.get(ev.detail["rid"])
                if n is None:
                    continue
                total += n * a + b * n * (n + 1) / 2 + head
        for d in self.decodes:
            for _row, (_rid, pos) in d.bound.items():
                total += ref.token_flops(cj, pos + 1) + head
        return total


def per_layer(c: Cell, win, summary, names: list, peak: dict) -> dict:
    view = RunView(c, win, summary, peak)
    out = {}
    for name in names:
        v = metric_reader(name)(view)
        if v is not None:
            out[name] = v
    return out


# ----------------------------------------------------------- correctness
def sample_requests(c: Cell, win, rng) -> list:
    """A seeded sample of the window's finished requests: the longest, one
    of each start path the window used, then one of each function not yet
    in it (requests of one function share their prompt and fed tokens, so
    a second one tells the reference nothing new)."""
    path = {}
    for e in c.engines.values():
        for ev in e.events:
            kind = {"prefill": "cold", "warm_start": "warm",
                    "restore": "restore"}.get(ev.kind)
            if kind:
                path[ev.detail["rid"]] = kind
    done = [r for r in win.records if r.in_window and r.done is not None
            and r.emitted == r.req.profile.decode_tokens]
    if not done:
        return []
    order = [done[i] for i in rng.permutation(len(done))]
    pick = [max(done, key=lambda r: (r.req.profile.prompt_tokens
                                     + r.req.profile.decode_tokens,
                                     r.req.rid))]
    for kind in ("cold", "warm", "restore"):
        pick += [r for r in order if path.get(r.req.rid) == kind
                 and r not in pick][:1]
    for r in order:
        if r.req.profile.name not in {q.req.profile.name for q in pick}:
            pick.append(r)
    for r in pick:
        r.path = path.get(r.req.rid, "cold")
    return pick


def served_tokens(c: Cell, win, picks: list) -> dict:
    """rid -> {position: greedy token} from the window's decode steps."""
    want = {r.req.rid for r in picks}
    hits = []
    for d in c.driver.decodes:
        for row, (rid, pos) in d.bound.items():
            if rid in want:
                hits.append((rid, pos, d, row))
    arrays = jax.device_get([d.served for d in c.driver.decodes])
    where = {id(d): i for i, d in enumerate(c.driver.decodes)}
    out: dict = {rid: {} for rid in want}
    for rid, pos, d, row in hits:
        out[rid][pos] = int(arrays[where[id(d)]][row])
    return out


def compare(c: Cell, picks: list, served: dict, control: bool) -> dict:
    """Run the reference once over each sampled request (its prompt and
    the tokens the engine fed it) and read, at each served position, how
    far the served token's logit lies below the reference's best: the
    mean over every served position of the sample, and the widest.  With
    ``control`` the reference is also run with every matmul's inputs in
    float8, and the token that one puts first is read the same way."""
    cj, ref = c.cj, c.ref
    S = max(f["prompt"] for t in c.mix["tenants"] for f in t["functions"]) \
        + max(f["decode"] for t in c.mix["tenants"] for f in t["functions"])
    n_out = max(f["decode"] for t in c.mix["tenants"]
                for f in t["functions"])
    key = jax.random.key(c.seed % (2 ** 32))
    w = jax.jit(lambda k: ref.make_weights(cj, k))(key)

    def gaps(w, toks, start, served, lowp):
        lg = ref.forward(cj, w, toks, start, n_out)
        best = lg.max(-1)
        g = best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        if not lowp:
            return g, g
        lo = ref.forward(cj, w, toks, start, n_out, lowp=True)
        pick = jnp.argmax(lo, -1)
        return g, best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]

    fn = jax.jit(gaps, static_argnums=(4,))
    out = {"by_path": {}, "widest": 0.0, "control_widest": 0.0,
           "tokens": 0}
    sums = {"program": 0.0, "control": 0.0}
    with jax.default_matmul_precision("highest"):
        for r in picks:
            prof = r.req.profile
            n, dn = prof.prompt_tokens, prof.decode_tokens
            toks = np.zeros(S, np.int32)
            toks[:n] = hash(prof.name) % 97 + 1
            got = served[r.req.rid]
            if sorted(got) != list(range(n, n + dn)):
                raise RuntimeError(f"{r.req.rid}: served positions "
                                   f"{len(got)} of {dn}")
            srv = np.zeros(n_out, np.int32)
            srv[:dn] = [got[p] for p in range(n, n + dn)]
            g, gc_ = jax.device_get(fn(w, jnp.asarray(toks), n,
                                       jnp.asarray(srv), control))
            widest = float(np.max(g[:dn]))
            out["widest"] = max(out["widest"], widest)
            out["control_widest"] = max(out["control_widest"],
                                        float(np.max(gc_[:dn])))
            sums["program"] += float(np.sum(g[:dn], dtype=np.float64))
            sums["control"] += float(np.sum(gc_[:dn], dtype=np.float64))
            wid, tot, k = out["by_path"].get(r.path, (0.0, 0.0, 0))
            out["by_path"][r.path] = (max(wid, widest), tot + float(
                np.sum(g[:dn], dtype=np.float64)), k + dn)
            out["tokens"] += dn
    out["mean"] = sums["program"] / out["tokens"]
    out["control_mean"] = sums["control"] / out["tokens"]
    out["by_path"] = {k: {"widest": wid, "mean": tot / n}
                      for k, (wid, tot, n) in out["by_path"].items()}
    return out


def limits_for(workload: str) -> dict:
    path = BENCH / "limits" / f"{workload}.json"
    return json.loads(path.read_text()) if path.is_file() else {}


def check(c: Cell, win, probe: list, control: bool) -> tuple[bool, dict,
                                                             dict]:
    """Every comparison of the run: (correct, checks, readings)."""
    lim = limits_for(c.name)
    checks, read = {}, {}
    errs = 0
    try:
        c.broker.check_invariants()
    except AssertionError as e:      # the ledger's own law broke
        errs = 1
        read["broker_error"] = str(e)[:300]
    checks["broker_invariant_errors"] = {"value": errs, "limit": 0}
    bad = int(sum(int(x) for x in jax.device_get(probe)))
    checks["restore_mismatch_elems"] = {"value": bad, "limit": 0}
    read["restores_checked"] = len(probe)
    rng = np.random.default_rng(np.random.SeedSequence([c.seed, 7]))
    picks = sample_requests(c, win, rng)
    served = served_tokens(c, win, picks)
    read["sampled"] = {r.req.rid: [r.path, r.req.profile.prompt_tokens,
                                   r.req.profile.decode_tokens]
                       for r in picks}
    free_program(c)
    t = time.perf_counter()
    limit = lim.get("mean_logit_gap", 0.0)
    if not picks:
        checks["mean_logit_gap"] = {"value": float("inf"), "limit": limit}
    else:
        cmp_ = compare(c, picks, served, control)
        # the control is judged in the program's place
        checks["mean_logit_gap"] = {
            "value": cmp_["control_mean" if control else "mean"],
            "limit": limit}
        read["logit_gap_by_path"] = cmp_["by_path"]
        read["widest_logit_gap"] = cmp_["widest"]
        read["served_tokens_compared"] = cmp_["tokens"]
        if control:
            read["program_mean_logit_gap"] = cmp_["mean"]
            read["control_widest_logit_gap"] = cmp_["control_widest"]
    read["reference_s"] = round(time.perf_counter() - t, 2)
    ok = all(v["value"] <= v["limit"] for v in checks.values())
    return ok, checks, read


def free_program(c: Cell) -> None:
    """Drop the program's state so the reference has the chip to itself."""
    for e in c.engines.values():
        e.caches = None
        e._decode_jit.clear()
        e._prefill_jit.clear()
        e.params = None
    c.params = None
    c.driver.decodes.clear()
    gc.collect()


# ------------------------------------------------------------------- main
def device_info(devs: list) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def prepare(workload: str, seed: int, bench: dict, cj_override=None,
            mix_override=None, rate=None) -> tuple[dict, Cell]:
    """The cell's entry, and its replicas built from its configuration
    and mix files (or the given overrides, for rehearsals)."""
    cell, conf = find_cell(bench, workload)
    cj = cj_override or json.loads((ROOT / conf["file"]).read_text())
    ref = load_module(BENCH / "configs" / f"{cj['reference']}.py",
                      f"bench_ref_{cj['reference']}")
    from bench import loadgen
    mix = mix_override or loadgen.load_mix(cell["traffic"])
    if rate is not None:
        mix = {**mix, "rate_per_s": float(rate)}
    return cell, build(workload, cj, ref, mix, seed)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, devs=None,
             bench: dict = None, cj_override=None, mix_override=None):
    """One run; returns the result line (a dict) and the stderr lines."""
    bench = bench or load_benchmark()
    from repro.kernels import kv_snapshot
    kv_snapshot.reset_stats()
    compiles()
    phases = [("start", T_START), ("imports", time.perf_counter())]
    cell, c = prepare(workload, seed, bench, cj_override, mix_override)
    phases.append(("weights+engines", time.perf_counter()))
    warm_shapes(c)
    phases.append(("compile warm", time.perf_counter()))
    serve_warmup(c)
    phases.append(("warm-up traffic", time.perf_counter()))
    probe = install_restore_probe(c)
    try:
        win = serve_window(c, seconds, trace)
    finally:
        c.restore_probe_undo()
    arrivals = [r for r in win.records if r.in_window]
    setup_s = win.t0 - T_START
    info = device_info(devs or jax.devices()[:cell["chips"]])
    e2e = end_to_end(c, win)
    n_orders = sum(1 for o in c.broker.orders.values()
                   if o.closed_at is not None
                   and win.t0 <= o.closed_at <= win.t0 + win.seconds)
    starts: dict = {}
    for rid, e in c.engines.items():
        a, b = win.event_span[rid]
        for ev in e.events[a:b]:
            starts[ev.kind] = starts.get(ev.kind, 0) + 1
    steps = c.driver.decodes[slice(*win.decode_span)]
    lines = ["set-up phases (s): " + ", ".join(
        f"{n} {b - a:.2f}" for (_, a), (n, b) in zip(phases, phases[1:])),
             f"window: attempted {len(arrivals)}, ticks {win.ticks}, "
             f"tokens {win.tokens_in_window}, new programs in window "
             f"{win.compiles[0]} (backend compiles {win.compiles[1]}), "
             f"orders closed in window {n_orders}, "
             f"snapshot stats {json.dumps(kv_snapshot.STATS)}",
             f"compiled in window: {win.compiled_names[:12]}",
             f"step events in window: {json.dumps(starts)}; live rows a "
             f"decode step: {sum(d.live for d in steps) / max(1, len(steps))}",
             f"backlog (s, pending): {win.backlog[::4]}",
             "queued, least over the window: "
             f"{min((n for _t, n in win.backlog), default=None)} "
             f"({json.dumps(c.mix['arrivals'])})"]
    summary = None
    if trace:
        from bench import tracing
        summary = tracing.reduce_trace(tracing.find_trace(TRACE_DIR),
                                       win.seconds)
        info["busy_s"] = summary.busy_s
        info["window_s"] = summary.window_s
    metrics = {}
    peak = None
    if trace:
        from bench import peaks
        peak = peaks.peaks(info["kind"]) if info["platform"] == "tpu" \
            else None
        names = [m["name"] for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
        vals = per_layer(c, win, summary, names, peak)
        units = {m["name"]: m["unit"] for m in bench["per_layer"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in vals.items()}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        for m in bench["end_to_end"]:
            if workload not in m.get("workloads", [workload]):
                continue
            v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    failed = sum(1 for r in arrivals
                 if r.done is None or r.emitted < r.req.profile.decode_tokens)
    ok, checks, read = check(c, win, probe, control)
    lines.append("readings: " + json.dumps(read))
    lines.append("window metrics: " + json.dumps(
        {k: v for k, v in e2e.items()} | {"setup_s": setup_s}))
    result = {"correct": bool(ok), "attempted": len(arrivals),
              "failed": failed, "metrics": metrics, "device": info}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    return result, lines


def sweep(workload: str, seed: int, seconds: float, rates: list, *,
          bench: dict = None, cj_override=None, mix_override=None) -> list:
    """Knee search: one set-up, then one window per offered rate; prints
    and returns each rate's backlog at the start and end of its window."""
    from bench import loadgen
    _cell, c = prepare(workload, seed, bench or load_benchmark(),
                       cj_override, mix_override, rate=rates[0])
    mix = c.mix
    warm_shapes(c)
    serve_warmup(c)
    out = []
    for i, r in enumerate(rates):
        c.mix = {**mix, "rate_per_s": r, "grace_s": 5.0}
        arr = loadgen.generate(c.mix, seed + i, seconds)
        win = serve_window(c, seconds, False, arrivals=arr)
        e2e = end_to_end(c, win)
        third = max(1, len(win.backlog) // 3)
        recs = [x for x in win.records if x.in_window]
        row = {"rate": r, "attempted": len(recs),
               "unfinished": sum(1 for x in recs if x.done is None),
               "no_first_token": sum(1 for x in recs if x.first is None),
               "backlog_first": float(np.mean(
                   [b for _t, b in win.backlog[:third]])),
               "backlog_last": float(np.mean(
                   [b for _t, b in win.backlog[-third:]])),
               "new_programs": win.compiles[0],
               "compiled": win.compiled_names[:6], **e2e}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None)
    args = ap.parse_args(argv)
    try:
        bench = load_benchmark()
        cell, _conf = find_cell(bench, args.workload)
        devs = require_accelerator(cell["chips"])
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"device: {devs[0].platform} {devs[0].device_kind} "
          f"x{len(devs)}; compile cache {cache}", file=sys.stderr)
    if args.sweep:
        sweep(args.workload, args.seed, args.seconds,
              [float(x) for x in args.sweep.split(",")])
        return 0
    result, lines = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace), control=bool(args.control),
                             devs=devs, bench=bench)
    for ln in lines:
        print(ln, file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
