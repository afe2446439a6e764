"""On-chip smoke run of the served GET path, checked against a plain oracle.

  python chip_smoke.py              # one TPU chip: served path + paper path
  python chip_smoke.py --chips 4    # four chips: shard_map GET vs the fused
                                    # one-device GET on the same store

Everything runs in this one process.  The data is generated from --seed:
OSM-shaped keys, and 64-byte values derived from each key and its write
version.  The store lives in a temporary directory that is removed at exit.

Default phases (one chip):

* served path: a 4-shard ``ShardedStore`` (shard boundaries at the data's
  quartiles, default ``StoreConfig`` with group-commit WAL) loaded with
  --keys keys in random order, then served through ``PipelinedServer``
  with an I/O pool: GETs of present and absent keys, a batch of PUTs and
  deletes, GETs of those keys from the memtable overlay, and the same
  GETs again from the rebuilt device snapshot after a flush;
* paper path: an in-memory ``BourbonStore`` (bourbon mode, CBA policy,
  every file learned) answering ``get_batch`` through ``LookupEngine``.

With --chips 4 only the four-chip phase runs: the same 4-shard store is
served once through the ``shard_map`` GET (one shard row per chip) and,
reopened from its directory, once through the fused one-device program.

Every answer (found flag and value bytes) is compared with the oracle; any
mismatch or failed phase exits non-zero.  The last line of output is one
JSON object naming the device; it is printed only when every phase passed.
Without a TPU the script exits non-zero before any phase runs.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

VALUE_SIZE = 64
# 8 odd 64-bit multipliers: value word i = key * MIX[i] + version, so every
# (key, version) pair gets its own 64 value bytes
_MIX = np.array([0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9,
                 0xD6E8FEB86659FD93, 0xFF51AFD7ED558CCD, 0xC4CEB9FE1A85EC53,
                 0x2545F4914F6CDD1D, 0x94D049BB133111EB], np.uint64)
SERVED_SHARDS = 4
GET_KEYS = 4096               # keys per served GET phase
GET_KEYS_PER_REQUEST = 8
WRITE_KEYS_PER_REQUEST = 64
# large write batches: each put_batch is one store tick, so fewer ticks
# means fewer files learned only to be compacted away during the load
LOAD_CHUNK = 1 << 18


class SmokeFailure(RuntimeError):
    pass


def value_bytes(keys: np.ndarray, version) -> np.ndarray:
    """(n, 64) uint8 value of each key at the given write version(s)."""
    k = np.asarray(keys, np.int64).astype(np.uint64)[:, None]
    ver = np.asarray(version, np.int64).astype(np.uint64).reshape(-1, 1)
    return np.ascontiguousarray(k * _MIX[None, :] + ver).view(np.uint8)


class Oracle:
    """Plain reference of the store's contents: the sorted loaded keys (all
    at version 0) plus a dict of every later write (version, or None for a
    delete)."""

    def __init__(self, keys: np.ndarray) -> None:
        self.keys = np.asarray(keys, np.int64)
        self.later: dict[int, int | None] = {}

    def put(self, keys: np.ndarray, version: int) -> None:
        self.later.update((int(k), version) for k in keys)

    def delete(self, keys: np.ndarray) -> None:
        self.later.update((int(k), None) for k in keys)

    def present(self, probes: np.ndarray) -> np.ndarray:
        idx = np.clip(np.searchsorted(self.keys, probes), 0,
                      self.keys.shape[0] - 1)
        return self.keys[idx] == probes

    def expect(self, probes: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        probes = np.asarray(probes, np.int64)
        found = self.present(probes)
        ver = np.zeros(probes.shape[0], np.int64)
        for i, p in enumerate(probes.tolist()):
            if p in self.later:
                v = self.later[p]
                found[i] = v is not None
                ver[i] = 0 if v is None else v
        return found, value_bytes(probes, ver)


def count_mismatches(oracle: Oracle, probes: np.ndarray, found: np.ndarray,
                     vals: np.ndarray) -> int:
    """Keys whose found flag or (when found) value bytes differ."""
    want_f, want_v = oracle.expect(probes)
    bad = found != want_f
    bad |= want_f & np.any(vals != want_v, axis=1)
    return int(bad.sum())


def absent_keys(rng, oracle: Oracle, n: int) -> np.ndarray:
    """n distinct keys the store does not hold: half next to a loaded key
    (inside a dense cluster, so they reach the learned-index descent unless
    a filter screens them), half spread over the key domain."""
    near = rng.choice(oracle.keys, n) + 1
    far = rng.integers(0, 1 << 50, n, dtype=np.int64)
    cand = np.unique(np.concatenate([near, far]))
    cand = cand[~oracle.present(cand)]
    return rng.permutation(cand)[:n]


def present_keys(rng, oracle: Oracle, n: int) -> np.ndarray:
    return oracle.keys[rng.choice(oracle.keys.shape[0], n, replace=False)]


# ------------------------------------------------------------------- serving

def serve(srv, requests) -> float:
    """Submit every request (ticking through backpressure), run the server
    until it is drained, and return the wall seconds taken."""
    t0 = time.perf_counter()
    i = 0
    while i < len(requests):
        if srv.submit(requests[i]):
            i += 1
        else:
            srv.tick()
    srv.run_until_drained()
    undone = [r.rid for r in requests if not r.done]
    if undone:
        raise SmokeFailure(f"{len(undone)} requests never completed")
    return time.perf_counter() - t0


class Client:
    """Builds requests with unique ids and checks GET answers."""

    def __init__(self, srv, oracle: Oracle) -> None:
        from repro.server import ServerRequest
        self._req = ServerRequest
        self.srv = srv
        self.oracle = oracle
        self.rid = 0

    def _split(self, op, keys, per, values=None):
        out = []
        for off in range(0, keys.shape[0], per):
            out.append(self._req(
                self.rid, op, keys[off: off + per],
                None if values is None else values[off: off + per]))
            self.rid += 1
        return out

    def get(self, phase: str, keys: np.ndarray):
        """Serve GETs of ``keys`` and check them; returns the answers."""
        reqs = self._split("get", keys, GET_KEYS_PER_REQUEST)
        secs = serve(self.srv, reqs)
        found = np.concatenate([r.found for r in reqs])
        vals = np.concatenate([r.result for r in reqs])
        bad = count_mismatches(self.oracle, keys, found, vals)
        print(f"[served] {phase}: {keys.shape[0]} GET keys "
              f"({int(found.sum())} found) in {secs:.3f} s, "
              f"mismatches={bad}", flush=True)
        if bad:
            raise SmokeFailure(f"{phase}: {bad} mismatches against the oracle")
        return found, vals

    def write(self, phase: str, puts: np.ndarray, version: int,
              deletes: np.ndarray) -> None:
        reqs = (self._split("put", puts, WRITE_KEYS_PER_REQUEST,
                            value_bytes(puts, version))
                + self._split("delete", deletes, WRITE_KEYS_PER_REQUEST))
        secs = serve(self.srv, reqs)
        self.oracle.put(puts, version)
        self.oracle.delete(deletes)
        print(f"[served] {phase}: {puts.shape[0]} PUT and "
              f"{deletes.shape[0]} DELETE keys acknowledged in {secs:.3f} s",
              flush=True)


def open_store(path: str, keys: np.ndarray, mesh="auto"):
    from repro.core import StoreConfig
    from repro.distributed import ShardedConfig, ShardedStore
    bounds = tuple(int(b) for b in np.quantile(
        keys, np.arange(1, SERVED_SHARDS) / SERVED_SHARDS))
    cfg = StoreConfig(wal_group_commit=True)
    if cfg.value_size != VALUE_SIZE:
        raise SmokeFailure(f"value_size {cfg.value_size} != {VALUE_SIZE}")
    return ShardedStore.open(path, ShardedConfig(n_shards=SERVED_SHARDS,
                                                 boundaries=bounds),
                             cfg, mesh=mesh)


def load(st, keys: np.ndarray, rng) -> None:
    from repro.io import IOPool
    t0 = time.perf_counter()
    order = rng.permutation(keys)
    pool = IOPool(SERVED_SHARDS)
    st.attach_io(pool)     # the shards apply their slices concurrently
    try:
        for off in range(0, order.shape[0], LOAD_CHUNK):
            ks = order[off: off + LOAD_CHUNK]
            st.put_batch(ks, value_bytes(ks, 0))
        st.flush_all()
        st.wal_sync()
    finally:
        st.detach_io()
        pool.close()
    print(f"[load] {keys.shape[0]} keys in random order: "
          f"{time.perf_counter() - t0:.1f} s", flush=True)


def build_state(st) -> dict:
    import jax
    t0 = time.perf_counter()
    state = jax.block_until_ready(st.device_state())
    secs = time.perf_counter() - t0
    nbytes = sum(int(v.nbytes) for v in state.values())
    devices = sorted({str(d) for v in state.values() for d in v.devices()})
    rows = np.asarray(state["n"]).tolist()
    print(f"[state] build {secs:.1f} s; {nbytes} bytes "
          f"({nbytes / 2**30:.3f} GiB) on {devices}; keys per shard {rows}",
          flush=True)
    print("[state] arrays " + ", ".join(
        f"{k}{tuple(v.shape)}:{v.dtype}" for k, v in sorted(state.items())),
        flush=True)
    platform = jax.devices()[0].platform
    if any(d.platform != platform for v in state.values()
           for d in v.devices()):
        raise SmokeFailure(f"device state is not resident on the {platform}")
    return state


def time_first_and_steady(st, oracle: Oracle, rng) -> None:
    """Time each probe-batch width the server pads to (64..1024) from cold,
    then the steady time of a 1024-probe batch."""
    firsts = {}
    for width in (64, 128, 256, 512, 1024):
        probes = present_keys(rng, oracle, width)
        t0 = time.perf_counter()
        found, vals = st.get_batch(probes, with_values=True)
        firsts[width] = time.perf_counter() - t0
        if count_mismatches(oracle, probes, found, vals):
            raise SmokeFailure(f"warm-up batch of {width} mismatched")
    steady = []
    for _ in range(5):
        probes = present_keys(rng, oracle, 1024)
        t0 = time.perf_counter()
        st.get_batch(probes, with_values=True)
        steady.append(time.perf_counter() - t0)
    print("[compile] first call per batch width (compile + run): "
          + ", ".join(f"{w}:{s:.3f}s" for w, s in firsts.items())
          + f"; total {sum(firsts.values()):.3f} s", flush=True)
    print(f"[compile] steady 1024-key batch: median "
          f"{np.median(steady) * 1e3:.3f} ms over {len(steady)} "
          f"(min {min(steady) * 1e3:.3f} ms)", flush=True)


def served_sequence(st, oracle: Oracle, rng):
    """The served-path phases on an open, loaded store; returns the final
    probe keys and their (found, values) answers."""
    n_get = GET_KEYS
    from repro.server import PipelineConfig, PipelinedServer
    srv = PipelinedServer(st, PipelineConfig(io_workers=4))
    try:
        cl = Client(srv, oracle)
        mixed = rng.permutation(np.concatenate([
            present_keys(rng, oracle, n_get // 2),
            absent_keys(rng, oracle, n_get // 2)]))
        cl.get("mixed present/absent", mixed)
        live = present_keys(rng, oracle, n_get)
        rewrite, delete = live[: n_get // 2], live[n_get // 2:]
        fresh = absent_keys(rng, oracle, n_get // 4)
        cl.write("writes", np.concatenate([rewrite, fresh]), 1, delete)
        touched = rng.permutation(np.concatenate([
            rewrite, fresh, delete, absent_keys(rng, oracle, n_get // 4)]))
        cl.get("after writes (memtable overlay)", touched)
        epoch = st.state_epoch
        st.flush_all()
        final = cl.get("after flush (device snapshot)", touched)
        if st.state_epoch == epoch:
            raise SmokeFailure("flush did not rebuild the device state")
        s = srv.stats()
        print(f"[served] server: batches={s['batches']} "
              f"store_probe_keys={s['store_probe_keys']} "
              f"served_from_cache={s['served_from_cache']} "
              f"epoch_violations={srv.epoch_violations}", flush=True)
        if srv.epoch_violations:
            raise SmokeFailure("pipelined server saw epoch violations")
        return touched, final
    finally:
        srv.shutdown()


# -------------------------------------------------------------------- phases

def served_phase(args, workdir: str) -> None:
    from repro.core import make_dataset
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    keys = make_dataset("osm", args.keys, seed=args.seed)
    print(f"[served] {args.keys} OSM-shaped keys generated in "
          f"{time.perf_counter() - t0:.1f} s (the paper loads 64M; cut to "
          f"{args.keys / 64e6:.3g}x so the host load fits one run)",
          flush=True)
    oracle = Oracle(keys)
    st = open_store(workdir + "/served", keys)
    print(f"[served] uses_shard_map={st.uses_shard_map}", flush=True)
    try:
        load(st, keys, rng)
        build_state(st)
        time_first_and_steady(st, oracle, rng)
        served_sequence(st, oracle, rng)
    finally:
        st.close()


def paper_phase(args) -> None:
    from repro.core import BourbonStore, StoreConfig, make_dataset
    rng = np.random.default_rng(args.seed + 1)
    keys = make_dataset("osm", args.keys // 4, seed=args.seed + 1)
    oracle = Oracle(keys)
    st = BourbonStore(StoreConfig(mode="bourbon", policy="cba",
                                  fetch_values=True))
    t0 = time.perf_counter()
    order = rng.permutation(keys)
    for off in range(0, order.shape[0], LOAD_CHUNK):
        ks = order[off: off + LOAD_CHUNK]
        st.put_batch(ks, value_bytes(ks, 0))
    st.flush_all()
    load_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    learned = st.learn_all()
    print(f"[paper] {keys.shape[0]} keys loaded in {load_s:.1f} s; "
          f"{learned} files learned in {time.perf_counter() - t0:.1f} s; "
          f"files per level {[len(lv) for lv in st.tree.levels]}",
          flush=True)
    times, bad = [], 0
    for _ in range(3):
        probes = rng.permutation(np.concatenate([
            present_keys(rng, oracle, 2048), absent_keys(rng, oracle, 2048)]))
        t0 = time.perf_counter()
        found, vals = st.get_batch(probes)
        times.append(time.perf_counter() - t0)
        bad += count_mismatches(oracle, probes, found, vals)
    s = st.stats()
    later = ", ".join(f"{t * 1e3:.3f} ms" for t in times[1:])
    print(f"[paper] get_batch of 4096 (half absent): first call "
          f"{times[0]:.3f} s, then {later}; "
          f"engine traces={st.engine.trace_count} "
          f"model_path={100 * s['model_path_frac']:.1f}% mismatches={bad}",
          flush=True)
    if st.engine.trace_count == 0:
        raise SmokeFailure("no lookup reached the LookupEngine")
    if bad:
        raise SmokeFailure(f"paper path: {bad} mismatches against the oracle")


def four_chip_phase(args, workdir: str) -> None:
    from repro.core import make_dataset
    from repro.distributed import ShardedStore
    rng = np.random.default_rng(args.seed)
    keys = make_dataset("osm", args.keys, seed=args.seed)
    print(f"[4chip] {args.keys} OSM-shaped keys (cut from the one-chip "
          f"default so the four-chip call stays short)", flush=True)
    oracle = Oracle(keys)
    path = workdir + "/served"
    st = open_store(path, keys)
    try:
        if not st.uses_shard_map:
            raise SmokeFailure("4 devices and 4 shards: expected the "
                               "shard_map GET")
        load(st, keys, rng)
        state = build_state(st)
        rows = {}
        for sh in state["keys"].addressable_shards:
            rows[sh.index[0].start or 0] = str(sh.device)
        print(f"[4chip] shard row -> device: {dict(sorted(rows.items()))}",
              flush=True)
        if len(set(rows.values())) != SERVED_SHARDS:
            raise SmokeFailure("shard rows do not sit on distinct devices")
        time_first_and_steady(st, oracle, rng)
        probes, (f_mesh, v_mesh) = served_sequence(st, oracle, rng)
        print(f"[4chip] uses_shard_map={st.uses_shard_map}", flush=True)
    finally:
        st.close()
    fused = ShardedStore.open(path, mesh=None)
    try:
        if fused.uses_shard_map:
            raise SmokeFailure("mesh=None must select the fused GET")
        from repro.server import PipelineConfig, PipelinedServer
        srv = PipelinedServer(fused, PipelineConfig(io_workers=4))
        try:
            f_one, v_one = Client(srv, oracle).get(
                "reopened, fused one-device GET", probes)
        finally:
            srv.shutdown()
    finally:
        fused.close()
    differ = int(np.sum((f_mesh != f_one)
                        | np.any(v_mesh != v_one, axis=1)))
    print(f"[4chip] shard_map vs fused answers differ on {differ} of "
          f"{probes.shape[0]} keys", flush=True)
    if differ:
        raise SmokeFailure("shard_map and fused GETs disagree")


# ---------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--keys", type=int, default=None,
                    help="keys in the sharded store (default 16M on one "
                         "chip, 4M on four); the paper path loads a quarter")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.keys is None:
        args.keys = 16_000_000 if args.chips == 1 else 4_000_000

    import jax
    import repro  # noqa: F401  (x64 on before any array is built)
    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"[device] {devices}", flush=True)
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print("no TPU found: this smoke run needs the chip", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{dev['count']}", file=sys.stderr)
        return 1

    print(f"[cache] compilation cache: {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="chip_smoke-")
    try:
        if args.chips == 4:
            four_chip_phase(args, workdir)
        else:
            served_phase(args, workdir)
            paper_phase(args)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
