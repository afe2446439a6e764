"""bourbonlint fixture suites: every rule fires on its positive snippet,
stays quiet on its negative twin, suppressions work only with a
justification, and the baseline round-trips add/expire."""

import json
import os
import sys
import textwrap

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.analysis import (SUPPRESS, apply_baseline, dead_module_report,
                            default_rules, load_baseline, make_baseline,
                            run_lint, save_baseline)
from repro.analysis.core import SourceFile
from repro.analysis.durorder import DurabilityOrderRule
from repro.analysis.hotsync import HotSyncRule
from repro.analysis.jitdisc import JitDisciplineRule
from repro.analysis.obsdrift import ObsDriftRule
from repro.analysis.pairing import PairingRule

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def lint_snippet(tmp_path, code, rules, name="snip.py", subdir=""):
    d = tmp_path / subdir if subdir else tmp_path
    d.mkdir(parents=True, exist_ok=True)
    p = d / name
    p.write_text(textwrap.dedent(code))
    return run_lint([str(p)], rules, root=str(tmp_path))


# ------------------------------------------------------------------ HOTSYNC

HOTSYNC_POS = """
    import numpy as np, jax, jax.numpy as jnp

    class PipeServer:
        def tick(self):
            dev = jnp.zeros((8,))
            host = np.asarray(dev)            # blocking transfer
            n = int(dev.sum())                # device coercion
            jax.device_get(dev)
            dev.block_until_ready()
            return host, n
"""

HOTSYNC_NEG = """
    import numpy as np, jax.numpy as jnp

    class PipeServer:
        def tick(self, batch):
            keys = np.asarray(batch.keys)     # host numpy: fine
            n = int(keys.sum())               # host coercion: fine
            dev = jnp.asarray(keys)           # host->device: fine
            return self.store.resolve_get(self.store.dispatch_get(dev))

    class Fleet:
        def resolve_get(self, pb):
            # the designated sync point may transfer its pending arg
            found = np.asarray(pb.f_dev)[: pb.n]
            return found

        def snapshot(self):
            dev = jnp.zeros((4,))
            return np.asarray(dev)            # not a registered hot path
"""


def test_hotsync_fires(tmp_path):
    fs = lint_snippet(tmp_path, HOTSYNC_POS, [HotSyncRule()])
    msgs = [f.message for f in fs if f.rule == "HOTSYNC"]
    assert len(msgs) == 4
    assert any("np.asarray" in m for m in msgs)
    assert any("int()" in m for m in msgs)
    assert any("device_get" in m for m in msgs)
    assert any("block_until_ready" in m for m in msgs)


def test_hotsync_quiet(tmp_path):
    fs = lint_snippet(tmp_path, HOTSYNC_NEG, [HotSyncRule()])
    assert [f for f in fs if f.rule == "HOTSYNC"] == []


# ----------------------------------------------------------------- DURORDER

DURORDER_POS = """
    import os

    def publish(path, data, fsync=True):
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)                    # no flush, no fsync
        os.replace(tmp, path)                # ... and no fsync_dir
"""

DURORDER_NEG = """
    import os
    from .format import fsync_dir

    def publish(path, data, fsync=True):
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            if fsync:
                os.fsync(f.fileno())
        os.replace(tmp, path)
        if fsync:
            fsync_dir(os.path.dirname(path))
"""


def durorder_rule():
    return DurabilityOrderRule(scopes=("storage",))


def test_durorder_fires(tmp_path):
    fs = lint_snippet(tmp_path, DURORDER_POS, [durorder_rule()],
                      subdir="storage")
    msgs = [f.message for f in fs if f.rule == "DURORDER"]
    assert any("flush+os.fsync" in m for m in msgs)          # TMPRENAME
    assert any("rename itself" in m for m in msgs)           # REPLACENODIR


def test_durorder_quiet(tmp_path):
    fs = lint_snippet(tmp_path, DURORDER_NEG, [durorder_rule()],
                      subdir="storage")
    assert [f for f in fs if f.rule == "DURORDER"] == []


def test_durorder_create_nosync(tmp_path):
    code = """
    import os

    def recover(path, fsync=True):
        with open(path, "ab") as f:          # new dir entry, never synced
            f.write(b"x")
    """
    fs = lint_snippet(tmp_path, code, [durorder_rule()], subdir="storage")
    assert any("fsync_dir" in f.message for f in fs)


def test_durorder_out_of_scope_quiet(tmp_path):
    # same code outside the storage scope is not durability-relevant
    fs = lint_snippet(tmp_path, DURORDER_POS, [durorder_rule()],
                      subdir="server")
    assert [f for f in fs if f.rule == "DURORDER"] == []


# ------------------------------------------------------------------ JITDISC

JITDISC_POS = """
    import jax

    class Engine:
        def build(self):
            for mode in self.modes:
                fn = jax.jit(lambda s, p: s + p)   # jit inside loop
            g = jax.jit(lambda x: x * self.scale)  # captures self.scale
            return g

    @jax.jit
    def probe(x):
        if x > 0:                                  # tracer truthiness
            return x
        return -x
"""

JITDISC_NEG = """
    import jax
    from functools import partial

    class Engine:
        def build(self, mode: str, slots: tuple):
            fn = partial(self._impl, mode=mode, slots=slots)
            return jax.jit(lambda s, p: fn(s, p))  # closes over locals only

    @partial(jax.jit, static_argnames=("mode",))
    def probe(x, mode):
        S = x.shape[-1]
        if mode == "model":                        # static: annotated arg
            return x
        if S <= 1024:                              # static: shape-derived
            return x * 2
        for i in range(3):                         # static unrolled loop
            x = x + i
        return -x
"""


def test_jitdisc_fires(tmp_path):
    fs = lint_snippet(tmp_path, JITDISC_POS, [JitDisciplineRule()])
    msgs = [f.message for f in fs if f.rule == "JITDISC"]
    assert any("inside a loop" in m for m in msgs)
    assert any("self state" in m and "self.scale" in m for m in msgs)
    assert any("truthiness" in m for m in msgs)


def test_jitdisc_quiet(tmp_path):
    fs = lint_snippet(tmp_path, JITDISC_NEG, [JitDisciplineRule()])
    assert [f for f in fs if f.rule == "JITDISC"] == []


def test_jitdisc_extra_traced(tmp_path):
    code = """
    class LookupEngine:
        def _lookup_impl(self, state, probes, mode: str):
            if probes:                     # tracer truthiness, no decorator
                return state
            return probes
    """
    fs = lint_snippet(tmp_path, code, [JitDisciplineRule()])
    assert any("truthiness" in f.message for f in fs)


# ------------------------------------------------------------------ PAIRING

PAIRING_POS = """
    class Server:
        def serve_discard(self, keys):
            self.store.dispatch_get(keys)          # dropped handle

        def serve_one_path(self, keys):
            pb = self.store.dispatch_get(keys)
            if pb.fast:
                return self.store.resolve_get(pb)
            return None                            # pb leaks on this path

        def fill_unstamped(self, keys, vals):
            self.cache.fill(keys, vals)            # no epoch stamp
"""

PAIRING_NEG = """
    class Server:
        def serve(self, keys):
            pb = self.store.dispatch_get(keys)
            if self._inflight and pb.epochs != self._epoch:   # test only
                self._flush()
            self._inflight.append(pb)              # escapes: consumed

        def serve_inline(self, keys):
            return self.store.resolve_get(self.store.dispatch_get(keys))

        def serve_branches(self, keys):
            pb = self.store.dispatch_get(keys)
            if self.eager:
                f, v = self.store.resolve_get(pb)
                return f, v
            return self._defer(pb)

        def fill_stamped(self, keys, vals, owners, epochs):
            self.cache.fill(keys, vals, owners, epochs)
"""


def test_pairing_fires(tmp_path):
    fs = lint_snippet(tmp_path, PAIRING_POS, [PairingRule()])
    msgs = [f.message for f in fs if f.rule == "PAIRING"]
    assert any("discarded" in m for m in msgs)
    assert any("every following path" in m for m in msgs)
    assert any("epoch stamp" in m for m in msgs)


def test_pairing_quiet(tmp_path):
    fs = lint_snippet(tmp_path, PAIRING_NEG, [PairingRule()])
    assert [f for f in fs if f.rule == "PAIRING"] == []


# ----------------------------------------------------------------- OBSDRIFT

OBSDRIFT_POS = """
    def attach(reg, tr):
        reg.counter("lookup_count")          # bad prefix, not *_total
        reg.gauge("store_files_total")       # gauge may not end _total
        c = reg.counter
        c("server_hits")                     # alias: counter not *_total
        reg.gauge("store_depth", region="eu")   # unknown label
        tr.stage("admissionz")               # not a READ_STAGE
        publish_stats(reg, "svr", {})        # undeclared prefix
        sp = tr.begin_span("walsync", bt)    # not a SPAN_NAME
        tr.end_span(sp, stage="fsync")       # not a CRITICAL_STAGE
"""

OBSDRIFT_NEG = """
    def attach(reg, tr, lb):
        reg.counter("server_gets_total", shard="0")
        reg.gauge("store_level_files", level="3", **lb)
        c = reg.counter
        c("cache_hits_total")
        reg.histogram("server_stage_us", stage="resolve")
        tr.stage("cache_probe")
        publish_stats(reg, "fleet", {})
        name = compute_name()
        reg.gauge(name)                      # dynamic: skipped
        sp = tr.begin_span("wal_sync", bt, link=bt, shard=0)
        tr.end_span(sp, stage="wal_fsync", retrack=True)
        tr.end_span(tr.begin_span(name, bt))    # dynamic name: skipped
"""


def obsdrift_rule():
    # fixture rule uses the built-in fallback declarations
    return ObsDriftRule()


def test_obsdrift_fires(tmp_path):
    fs = lint_snippet(tmp_path, OBSDRIFT_POS, [obsdrift_rule()])
    msgs = [f.message for f in fs if f.rule == "OBSDRIFT"]
    assert any("layer prefix" in m for m in msgs)
    assert any("'_total'" in m and "gauge" in m for m in msgs)
    assert any("server_hits" in m for m in msgs)      # alias tracked
    assert any("label 'region'" in m for m in msgs)
    assert any("READ_STAGES" in m for m in msgs)
    assert any("publish_stats prefix" in m for m in msgs)
    assert any("SPAN_NAMES" in m for m in msgs)
    assert any("CRITICAL_STAGES" in m for m in msgs)


def test_obsdrift_quiet(tmp_path):
    fs = lint_snippet(tmp_path, OBSDRIFT_NEG, [obsdrift_rule()])
    assert [f for f in fs if f.rule == "OBSDRIFT"] == []


def test_obsdrift_reads_live_declarations():
    rule = ObsDriftRule.from_root(REPO)
    assert "value_fetch" in rule.stages       # parsed from obs/__init__.py
    assert "fleet" in rule.prefixes           # parsed from obs/README.md
    assert "index" in rule.labels
    assert "shard_probe" in rule.spans        # parsed from obs/trace.py
    assert "wal_fsync" in rule.critical
    # code and README causal-tracing tables agree (drift would be
    # reported as findings against trace.py)
    assert rule._trace_drift == []


def test_obsdrift_compares_readme_stage_table(tmp_path):
    """The README's stage table is parsed (the table follows its heading
    after a blank line) and checked against READ_STAGES both ways."""
    readme = os.path.join(REPO, "src", "repro", "obs", "README.md")
    init = os.path.join(REPO, "src", "repro", "obs", "__init__.py")
    rule = ObsDriftRule(obs_init=init, obs_readme=readme)
    assert rule._stage_drift is None
    assert "retire" in rule.stages and "generation" in rule.labels
    with open(readme, encoding="utf-8") as f:
        text = f.read()
    bad = tmp_path / "README.md"
    bad.write_text(text.replace("| `complete`", "| `completion`"))
    rule = ObsDriftRule(obs_init=init, obs_readme=str(bad))
    _, code, table = rule._stage_drift
    assert "complete" in code and "completion" in table


# ------------------------------------------------------------- suppressions

def test_suppression_honored(tmp_path):
    code = """
    import numpy as np, jax.numpy as jnp

    class PipeServer:
        def tick(self):
            dev = jnp.zeros((4,))
            # bourbonlint: allow[HOTSYNC] -- stats snapshot, off hot path
            return np.asarray(dev)
    """
    fs = lint_snippet(tmp_path, code, [HotSyncRule()])
    hot = [f for f in fs if f.rule == "HOTSYNC"]
    assert len(hot) == 1 and hot[0].suppressed
    assert not [f for f in fs if f.rule == SUPPRESS]


def test_suppression_without_justification_rejected(tmp_path):
    code = """
    import numpy as np, jax.numpy as jnp

    class PipeServer:
        def tick(self):
            dev = jnp.zeros((4,))
            return np.asarray(dev)  # bourbonlint: allow[HOTSYNC]
    """
    fs = lint_snippet(tmp_path, code, [HotSyncRule()])
    hot = [f for f in fs if f.rule == "HOTSYNC"]
    assert len(hot) == 1 and not hot[0].suppressed    # NOT suppressed
    supp = [f for f in fs if f.rule == SUPPRESS]
    assert len(supp) == 1 and "justification" in supp[0].message


def test_suppress_finding_not_suppressible(tmp_path):
    code = """
    # bourbonlint: allow[SUPPRESS] -- should not work
    # bourbonlint: allow[HOTSYNC]
    x = 1
    """
    fs = lint_snippet(tmp_path, code, [HotSyncRule()])
    supp = [f for f in fs if f.rule == SUPPRESS]
    assert len(supp) == 1 and not supp[0].suppressed


# ----------------------------------------------------------------- baseline

def test_baseline_add_expire_roundtrip(tmp_path):
    bl_path = str(tmp_path / "bl.json")
    rules = [HotSyncRule()]

    fs = lint_snippet(tmp_path, HOTSYNC_POS, rules)
    assert len(fs) == 4 and not any(f.baselined for f in fs)

    # add: baseline covers today's findings; rerun is green
    save_baseline(bl_path, make_baseline(fs))
    fs2 = lint_snippet(tmp_path, HOTSYNC_POS, rules)
    expired = apply_baseline(fs2, load_baseline(bl_path))
    assert all(f.baselined for f in fs2) and expired == []

    # a *new* violation of the same rule is not covered
    extra = HOTSYNC_POS + """
        def dispatch_more(self):
            return np.asarray(jnp.ones(2))
    """
    fs3 = lint_snippet(tmp_path, extra, rules)
    apply_baseline(fs3, load_baseline(bl_path))
    new = [f for f in fs3 if not f.baselined]
    assert len(new) == 1 and "dispatch_more" in new[0].symbol

    # expire: fixing the code leaves dangling baseline entries to prune
    fs4 = lint_snippet(tmp_path, HOTSYNC_NEG, rules)
    expired = apply_baseline(fs4, load_baseline(bl_path))
    assert len(expired) == 4
    save_baseline(bl_path, make_baseline(fs4))
    assert load_baseline(bl_path)["findings"] == []


def test_repo_baseline_is_empty():
    with open(os.path.join(REPO, ".bourbonlint-baseline.json")) as f:
        assert json.load(f)["findings"] == []


# ------------------------------------------------------------- repo-level

def test_repo_lints_clean():
    """The production gate: zero unbaselined findings on src/repro."""
    rules = default_rules(REPO)
    fs = run_lint([os.path.join(REPO, "src", "repro")], rules, root=REPO)
    new = [f for f in fs if not f.suppressed and not f.baselined]
    assert new == [], "\n" + "\n".join(f.render() for f in new)


def test_dead_module_report():
    rep = dead_module_report(REPO)
    assert rep["dead"] == [], rep["dead"]      # allowlist covers the rest
    assert rep["reachable"] > 50
    # the quarantined seed leftovers really are flagged, not forgotten
    assert any(m.startswith("repro.configs.") for m in rep["quarantined"])


def test_parse_error_reported(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def broken(:\n")
    fs = run_lint([str(p)], [HotSyncRule()], root=str(tmp_path))
    assert len(fs) == 1 and fs[0].rule == "PARSE"
