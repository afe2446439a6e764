"""One run of one cell: set-up, measured window, check, metrics.

The system under test is the program's served path: ``PipelinedServer``
(``submit`` / ``tick``) over a durable 4-shard ``ShardedStore`` in a
temporary directory.  Everything else -- data, traffic, the reference
and the reading of metrics -- lives in this directory.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import gen
from .oracle import Checker, Oracle
from .spec import BENCH_DIR, Cell, generator_kind, read_metrics

ROOT = BENCH_DIR.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

import repro  # noqa: E402,F401  (x64 on before any array is built)
from repro.server import ServerRequest  # noqa: E402

LOAD_CHUNK = 1 << 18          # keys per put_batch while loading
PAD_WIDTHS = (64, 128, 256, 512, 1024)   # every probe pad the server uses
DRAIN_S = 60.0                # how long answers due in the window may lag
READBACK_KEYS = 4096          # acknowledged writes read back after the window
ROLL_KEYS = 4096              # loaded keys rewritten after the window
ROLL_KEYS_PER_PUT = 64        # keys per write request of that step
MIN_COMPARED = 100            # a check that compares fewer keys fails
_now = time.perf_counter


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass(eq=False)
class BenchRequest(ServerRequest):
    due: float = 0.0          # when the request was due (open loop) or sent
    sub_t: float = -1.0       # when the server accepted it
    done_t: float = -1.0      # when the tick that answered it returned
    version: int = 0          # write version of a put
    seq: int = -1             # submission order


class RequestMaker:
    """Draws each request's op and keys from the mix, in bulk."""

    def __init__(self, mix: dict, keys: np.ndarray, rng, values) -> None:
        self.keys = keys                      # indexed by the distribution
        self.read_fraction = float(mix["read_fraction"])
        self.kpr = int(mix["keys_per_request"])
        self.dist = gen.distribution(mix["distribution"], keys.shape[0], mix)
        self.rng = rng
        self.values = values
        self.rid = 0
        self.version = 0
        self._coins = iter(())
        self._idx = iter(())

    def _coin(self) -> float:
        try:
            return next(self._coins)
        except StopIteration:
            self._coins = iter(self.rng.random(8192).tolist())
            return next(self._coins)

    def _keys(self) -> np.ndarray:
        out = np.empty(self.kpr, np.int64)
        for i in range(self.kpr):
            try:
                out[i] = next(self._idx)
            except StopIteration:
                self._idx = iter(self.keys[self.dist.draw(self.rng, 8192)]
                                 .tolist())
                out[i] = next(self._idx)
        return out

    def __call__(self, due: float) -> BenchRequest:
        self.rid += 1
        keys = self._keys()
        if self._coin() < self.read_fraction:
            return BenchRequest(self.rid, "get", keys, due=due)
        self.version += 1
        return BenchRequest(self.rid, "put", keys,
                            self.values(keys, self.version), due=due,
                            version=self.version)


class Recorder:
    """What the window's requests did: latencies, generator lag, a seeded
    sample of GET answers for the check, and every write in order."""

    def __init__(self, checker: Checker, rng, sample_requests: int) -> None:
        self.checker = checker
        self.rng = rng
        self.k = int(sample_requests)
        self.sample: list = []
        self.n_window_gets = 0
        self.t0 = self.t1 = math.inf
        self.seq = 0
        self.get_lat: list = []
        self.put_lat: list = []
        self.gen_lag: list = []
        self.window_keys_done = 0
        self.attempted = 0
        self.outstanding: set = set()
        self.roll_seq = None          # first request after the memtable roll
        self.max_tick = 0.0
        self.slow_ticks = 0

    def tick_took(self, t0: float, t1: float) -> None:
        if self.t0 <= t0 < self.t1:
            self.max_tick = max(self.max_tick, t1 - t0)
            self.slow_ticks += t1 - t0 > 0.02

    def in_window(self, r: BenchRequest) -> bool:
        return self.t0 <= r.due < self.t1

    def submitted(self, r: BenchRequest, now: float) -> None:
        r.sub_t = now
        r.seq = self.seq
        self.seq += 1
        if r.op != "get":
            self.checker.write(r.seq, r.op, r.keys, r.version)
        if self.in_window(r):
            self.attempted += 1
            self.outstanding.add(r.rid)
            self.gen_lag.append(now - r.due)

    def done(self, r: BenchRequest, now: float) -> None:
        r.done_t = now
        if r.op == "get" and self.t0 <= now < self.t1:
            self.window_keys_done += r.keys.shape[0]
        if not self.in_window(r):
            return
        self.outstanding.discard(r.rid)
        lat = now - r.due
        if r.op != "get":
            self.put_lat.append(lat)
            return
        self.get_lat.append(lat)
        # reservoir sample of the window's GETs, drawn from the seed
        item = (r.seq, r.keys, r.found, r.result)
        if len(self.sample) < self.k:
            self.sample.append(item)
        else:
            j = int(self.rng.integers(0, self.n_window_gets + 1))
            if j < self.k:
                self.sample[j] = item
        self.n_window_gets += 1


class Spans:
    """The harness's own profiler spans (on in the traced run only)."""

    def __init__(self, on: bool) -> None:
        self.on = on

    def __call__(self, name: str):
        if self.on:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


def drive(srv, gen_, rec: Recorder, t_stop: float, pending: collections.deque,
          spans: Spans, accept_new: bool = True) -> None:
    """Serve until ``t_stop``: send what is due (a full queue keeps it
    waiting, in order), tick while the server holds work, and sleep to the
    next arrival when it holds none."""
    while True:
        now = _now()
        if now >= t_stop:
            return
        if accept_new:
            pending.extend(gen_.poll(now))
        if pending:
            with spans("bench.submit"):
                while pending:
                    r = pending[0]
                    if not srv.submit(r):
                        break
                    rec.submitted(r, now)
                    pending.popleft()
        if len(srv.queue) or srv.inflight:
            with spans("bench.tick"):
                done = srv.tick()
            t = _now()
            rec.tick_took(now, t)
            with spans("bench.collect"):
                for r in done:
                    rec.done(r, t)
                    gen_.completed(r, t)
        elif not accept_new and not pending:
            return
        else:
            nxt = gen_.next_due() if accept_new else None
            if nxt is not None and nxt > now:
                with spans("bench.idle"):
                    time.sleep(min(nxt - now, 1e-3))


# ------------------------------------------------------------------ set-up

class Setup:
    """Named set-up phases with their seconds."""

    def __init__(self) -> None:
        self.phases: list = []

    def phase(self, name: str, t0: float) -> None:
        self.phases.append((name, _now() - t0))
        log(f"[setup] {name}: {self.phases[-1][1]:.3f} s")


def make_data(cfg: dict, seed: int):
    """Keys (sorted, and in the order the traffic indexes them), the
    loaded set's load order and the value maker, all from the seed."""
    rng = np.random.default_rng([seed, 1])
    n = int(cfg["keys"])
    keys = gen.KEY_SETS[cfg["key_set"]](n, rng)
    by_index = keys.copy()               # the distribution's index order
    order = (rng.permutation(keys) if cfg["load_order"] == "random"
             else keys.copy())
    values = gen.ValueMaker(int(cfg["value_size"]), seed)
    return np.sort(keys), by_index, order, values


def open_store(cfg: dict, sorted_keys: np.ndarray, path: str):
    from repro.core import StoreConfig
    from repro.core.filters import FilterConfig
    from repro.distributed import ShardedConfig, ShardedStore
    n_sh = int(cfg["shards"])
    bounds = tuple(int(b) for b in np.quantile(
        sorted_keys, np.arange(1, n_sh) / n_sh).astype(np.int64))
    dur = cfg["durability"]
    scfg = StoreConfig(value_size=int(cfg["value_size"]),
                       wal_group_commit=dur["wal"] == "group_commit",
                       fsync=bool(dur["fsync"]),
                       filters=FilterConfig(enabled=bool(cfg["bloom_filters"])))
    return ShardedStore.open(path, ShardedConfig(
        n_shards=n_sh, boundaries=bounds, delta=int(cfg["plr_delta"])),
        scfg, mesh="auto")


def load(st, order: np.ndarray, values) -> None:
    from repro.io import IOPool
    pool = IOPool(st.n_shards)
    st.attach_io(pool)        # the shards apply their slices side by side
    try:
        for off in range(0, order.shape[0], LOAD_CHUNK):
            ks = order[off: off + LOAD_CHUNK]
            st.put_batch(ks, values(ks, 0))
        st.flush_all()
        st.wal_sync()
    finally:
        st.detach_io()
        pool.close()


def warm_widths(st, sorted_keys: np.ndarray, rng) -> None:
    """Compile and run the GET programs at every pad width."""
    for w in PAD_WIDTHS:
        probes = sorted_keys[rng.choice(sorted_keys.shape[0], w,
                                        replace=False)]
        st.get_batch(probes, with_values=True)


class CompileCounter:
    """Counts traces and backend compiles while armed."""

    def __init__(self) -> None:
        from jax._src import monitoring
        self.armed = False
        self.traces = 0
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if not self.armed:
            return
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


class GcPauses:
    """Python's generation-2 collections, and the time all collections
    paused the process, while armed."""

    def __init__(self) -> None:
        self.armed = False
        self.gen2 = 0
        self.seconds = 0.0
        self._t = 0.0
        gc.callbacks.append(self._on)

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if not self.armed:
            return
        if phase == "start":
            self._t = _now()
        elif self._t:
            self.seconds += _now() - self._t
            self.gen2 += info.get("generation") == 2
            self._t = 0.0


# ----------------------------------------------------------------- the run

def obs_delta(snap0: dict, snap1: dict, name: str):
    """Change of an obs counter or gauge (summed over its label sets)
    between two registry snapshots; None when it was never set."""
    def total(snap):
        fam = snap.get(name)
        if fam is None or not fam["samples"]:
            return None
        return float(sum(s["value"] for s in fam["samples"]))

    a, b = total(snap0), total(snap1)
    return None if b is None else b - (a or 0.0)


class Context:
    """What a metric reader reads: the run's records, the obs registry's
    snapshots at the window's ends, and the reduced device trace."""

    def __init__(self, cell: Cell, seconds: float, rec: Recorder,
                 setup_s: float, snap0: dict, snap1: dict,
                 trace: dict | None, device_kind: str) -> None:
        self.cell = cell
        self.seconds = seconds
        self.rec = rec
        self.setup_s = setup_s
        self.snap0 = snap0
        self.snap1 = snap1
        self.trace = trace
        self.device_kind = device_kind

    def delta(self, name: str):
        return obs_delta(self.snap0, self.snap1, name)

    def stage(self, name: str):
        """(sampled microseconds, sampled count) of a ``StageTracer``
        stage across the window."""
        fam = "server_stage_us"
        get = {}
        for label, snap in (("a", self.snap0), ("b", self.snap1)):
            s = [x["value"] for x in snap.get(fam, {}).get("samples", [])
                 if x["labels"].get("stage") == name]
            get[label] = s[0] if s else None
        if get["b"] is None:
            return None
        a = get["a"] or {"sum": 0.0, "count": 0}
        return get["b"]["sum"] - a["sum"], get["b"]["count"] - a["count"]


def percentile_ms(xs: list, q: float):
    return float(np.percentile(np.asarray(xs), q)) * 1e3 if xs else None


class System:
    """The system under test built from the seed: data, the store loaded
    and its device state and GET programs ready, and the server over it.
    ``server_factory`` puts another server in the program's place (the
    control); ``store_hook`` may alter the ready store (the fault tests).
    Closing it stops the server, closes the store and removes its files."""

    def __init__(self, cell: Cell, seed: int, setup: Setup,
                 server_factory=None, store_hook=None) -> None:
        from repro.server import PipelineConfig, PipelinedServer
        cfg = cell.config
        self.workdir = tempfile.mkdtemp(prefix="bench-store-")
        self.srv = self.st = None
        try:
            t = _now()
            self.sorted_keys, self.by_index, order, self.values = make_data(
                cfg, seed)
            setup.phase(f"data ({self.sorted_keys.shape[0]} keys)", t)
            if server_factory is not None:
                self.srv = server_factory(cfg, self.sorted_keys, self.values)
                return
            t = _now()
            self.st = open_store(cfg, self.sorted_keys,
                                 self.workdir + "/store")
            load(self.st, order, self.values)
            setup.phase("load", t)
            t = _now()
            jax.block_until_ready(self.st.device_state())
            setup.phase("device state", t)
            t = _now()
            warm_widths(self.st, self.sorted_keys,
                        np.random.default_rng([seed, 2]))
            setup.phase("GET programs at every pad width", t)
            # a store serving for long has learned the files it was loaded
            # with; learning them here keeps that backlog out of the window
            t = _now()
            self.st.learn_all()
            setup.phase("learn the loaded files", t)
            if store_hook is not None:
                store_hook(self.st)
            self.srv = PipelinedServer(self.st, PipelineConfig(
                io_workers=int(cfg["io_workers"])))
        except BaseException:
            self.close()
            raise

    def close(self) -> None:
        if self.srv is not None and hasattr(self.srv, "shutdown"):
            self.srv.shutdown()
        if self.st is not None:
            self.st.close()
        self.srv = self.st = None
        shutil.rmtree(self.workdir, ignore_errors=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, server_factory=None, store_hook=None) -> dict:
    """One run; returns the result line (see ``System`` for the hooks)."""
    cfg, mix = cell.config, cell.traffic
    setup = Setup()
    counter = CompileCounter()
    spans = Spans(trace)
    system = pauses = None
    try:
        system = System(cell, seed, setup, server_factory, store_hook)
        srv, st = system.srv, system.st
        rng = np.random.default_rng([seed, 3])
        make_req = RequestMaker(mix, system.by_index, rng, system.values)
        gen_ = generator_kind(mix["kind"]).make(mix, rng, make_req)
        rec = Recorder(Checker(Oracle(system.sorted_keys), system.values),
                       np.random.default_rng([seed, 4]),
                       int(mix["check_requests"]))
        pending: collections.deque = collections.deque()
        t = _now()
        gen_.start(t)
        drive(srv, gen_, rec, t + float(mix["warmup_s"]), pending,
              Spans(False))
        setup.phase("traffic warm-up", t)
        probe_log = instrument(st, spans) if trace and st else None
        gc.collect()                  # the set-up's own garbage
        pauses = GcPauses()
        snap0 = snapshot(srv)
        counter.armed = pauses.armed = True
        rec.t0 = _now()
        rec.t1 = rec.t0 + seconds
        setup_s = rec.t0 - t_start
        tr = None
        if trace:
            # the traced run's window is the traced slice: stopping the
            # profiler blocks the host, so nothing is measured after it
            tr = TraceSlice(min(seconds, float(mix.get("trace_s", seconds))))
            rec.t1 = rec.t0 + tr.seconds
            if probe_log is not None:
                probe_log.on = True
            tr.start()
            with spans("bench.window"):
                drive(srv, gen_, rec, rec.t1, pending, spans)
            snap1 = snapshot(srv)
            tr.stop()
            if probe_log is not None:
                probe_log.on = False
        else:
            drive(srv, gen_, rec, rec.t1, pending, spans)
            snap1 = snapshot(srv)
        counter.armed = pauses.armed = False
        # answers due in the window may come late, but must come
        drive(srv, gen_, rec, _now() + DRAIN_S, pending, spans,
              accept_new=False)
        for r in pending:             # due in the window, never sent
            rec.attempted += 1
            rec.outstanding.add(r.rid)
        peak = memory_peak()
        learned = obs_delta(snap0, snap1, "fleet_files_learned")
        log(f"[window] {rec.t1 - rec.t0:.1f} s: {rec.attempted} requests "
            f"due, {rec.n_window_gets} GETs answered; inside the window: "
            f"traces={counter.traces} compiles={counter.compiles}, "
            f"ticks over 20 ms={rec.slow_ticks} (longest "
            f"{rec.max_tick * 1e3:.1f} ms), gc gen2={pauses.gen2} "
            f"(pauses {pauses.seconds * 1e3:.1f} ms), files learned="
            f"{learned}")
        rolled, after_roll = check_roll(system, make_req, rec, mix, seed)
        unanswered = len(rec.outstanding)
        device_trace = None
        if trace:
            from .tracing import summarize
            from .work import get_work
            work = None
            if probe_log is not None:
                state = st.device_state()
                work = get_work(probe_log.n_miss, np.asarray(state["nseg"]),
                                int(cfg["plr_delta"]),
                                int(st.shards[0].cfg.lsm.bloom_k),
                                "fbits" in state)
            try:
                device_trace = summarize(tr.xplane(), work)
            finally:
                tr.cleanup()
    finally:
        counter.close()
        if pauses is not None:
            pauses.close()
        if system is not None:
            system.close()
    # the reference runs once the program's state is gone
    for seq, keys, found, vals in rec.sample:
        rec.checker.read(seq, keys, found, vals)
    compared, wrong, wrong_reads, wrong_after = rec.checker.replay(
        rec.roll_seq)
    for d in rec.checker.wrong_detail[:8]:
        log("[wrong] key={} want found={} version={} got found={} "
            "value[:8] want={} got={}".format(*d))
    n_shards = int(cfg["shards"])
    check = {
        "wrong_keys": {"value": wrong, "max": 0},
        "wrong_keys_after_roll": {"value": wrong_after, "max": 0},
        "unanswered_requests": {"value": unanswered, "max": 0},
        "compared_keys": {"value": compared, "min": MIN_COMPARED},
        "keys_read_after_roll": {"value": after_roll, "min": MIN_COMPARED},
        "rolled_shards": {"value": rolled, "min": n_shards},
    }
    correct = (wrong == 0 and unanswered == 0 and compared >= MIN_COMPARED
               and after_roll >= MIN_COMPARED and rolled >= n_shards)
    devs = jax.devices()
    ctx = Context(cell, seconds, rec, setup_s, snap0, snap1, device_trace,
                  devs[0].device_kind)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, ctx)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": rec.attempted,
           "failed": unanswered + wrong_reads, "metrics": metrics,
           "device": device}
    if device_trace is not None:
        device["busy_s"] = device_trace["busy_s"]
        device["window_s"] = device_trace["window_s"]
        out["breakdown"] = device_trace["breakdown"]
    out["setup_phases"] = {n: s for n, s in setup.phases}
    out["check"] = check
    return out


def snapshot(srv) -> dict:
    obs = getattr(srv, "obs", None)
    return obs.registry.snapshot() if obs is not None else {}


def memory_peak() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def serve_all(srv, rec: Recorder, reqs: list) -> None:
    """Submit ``reqs`` in order and tick until each is answered (or
    ``DRAIN_S`` has passed); an unanswered one counts as outstanding."""
    todo = collections.deque(reqs)
    t_stop = _now() + DRAIN_S
    while (todo or len(srv.queue) or srv.inflight) and _now() < t_stop:
        while todo and srv.submit(todo[0]):
            rec.submitted(todo.popleft(), _now())
        srv.tick()
    for r in reqs:
        if not r.done:
            rec.outstanding.add(r.rid)


def read_back(srv, rec: Recorder, keys: np.ndarray, kpr: int) -> int:
    """GET ``keys`` through the server, ``kpr`` to a request; each answer
    joins the comparison.  Returns the number of keys asked for."""
    base = -1 - rec.seq
    reqs = [BenchRequest(base - i, "get", keys[off: off + kpr])
            for i, off in enumerate(range(0, keys.shape[0], kpr))]
    serve_all(srv, rec, reqs)
    for r in reqs:
        if r.done:
            rec.checker.read(r.seq, r.keys, r.found, r.result)
    return int(keys.shape[0])


def roll(system) -> int:
    """Roll every shard's memtable through the store's own flush path and
    rebuild the device state; returns the number of shards whose
    snapshot was rebuilt."""
    st = system.st
    if st is None:                    # the reference in the program's place
        return system.srv.roll()
    t = _now()
    before = st.shard_epochs()
    st.flush_all()
    st.wal_sync()
    jax.block_until_ready(st.device_state())
    rolled = sum(a != b for a, b in zip(before, st.shard_epochs()))
    log(f"[roll] {rolled} shards rolled and the device state rebuilt in "
        f"{_now() - t:.2f} s")
    return rolled


def check_roll(system, make_req: RequestMaker, rec: Recorder, mix: dict,
               seed: int) -> tuple:
    """After the window: rewrite a seeded sample of loaded keys through
    the server, read the run's written keys back from the memtable
    overlay, roll every memtable into the device snapshot, and read the
    written keys and as many unwritten loaded keys back from the rebuilt
    snapshot.  Returns (shards rolled, keys read after the roll)."""
    rng = np.random.default_rng([seed, 5])
    loaded = system.sorted_keys
    n_roll = min(ROLL_KEYS, loaded.shape[0] // 4)
    keys = rng.choice(loaded, n_roll, replace=False)
    puts = []
    for off in range(0, n_roll, ROLL_KEYS_PER_PUT):
        ks = keys[off: off + ROLL_KEYS_PER_PUT]
        make_req.version += 1
        puts.append(BenchRequest(-1 - rec.seq - len(puts), "put", ks,
                                 system.values(ks, make_req.version),
                                 version=make_req.version))
    srv = system.srv
    serve_all(srv, rec, puts)
    written = np.unique(np.concatenate(
        [p[0] for _, op, p in rec.checker.events if op == "put"]))
    kpr = int(mix["keys_per_request"])
    read_back(srv, rec, rng.permutation(written)[:READBACK_KEYS], kpr)
    rolled = roll(system)
    rec.roll_seq = rec.seq
    unwritten = np.setdiff1d(loaded, written, assume_unique=True)
    after = np.concatenate([
        rng.permutation(written)[:READBACK_KEYS],
        rng.choice(unwritten, min(READBACK_KEYS, unwritten.shape[0]),
                   replace=False)])
    return rolled, read_back(srv, rec, rng.permutation(after), kpr)


class ProbeLog:
    """Device probe count of each store dispatch while ``on``."""

    def __init__(self) -> None:
        self.on = False
        self.n_miss: list = []


def instrument(st, spans: Spans) -> ProbeLog:
    """Traced run only: span the store's dispatch and state refresh, and
    record each dispatch's device probe count for the work count."""
    plog = ProbeLog()
    dispatch_get, device_state = st.dispatch_get, st.device_state

    def traced_dispatch(probes, *a, **kw):
        with spans("bench.dispatch_get"):
            pb = dispatch_get(probes, *a, **kw)
        if plog.on and pb.f_dev is not None:
            plog.n_miss.append(pb.n_miss)
        return pb

    def traced_state():
        with spans("bench.device_state"):
            return device_state()

    st.dispatch_get = traced_dispatch
    st.device_state = traced_state
    return plog


class TraceSlice:
    """A profiler trace of the first ``seconds`` of the window, written to
    a temporary directory that is removed once it has been read."""

    def __init__(self, seconds: float) -> None:
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        # no Python call tracing and only the host's top-level events (the
        # harness's spans among them): both slow the host under trace
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        jax.profiler.stop_trace()

    def xplane(self) -> Path:
        found = sorted(Path(self.dir).glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return found[-1]

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
