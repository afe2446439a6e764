"""The check sees a broken timed path: each fault the cells can have is
planted under the harness, and ``correct`` has to come out false.

(The cells run on one chip, so the fault of a skipped exchange between
chips has no place here.)"""

import numpy as np
import pytest

from ._tiny import CELLS, run_tiny


def stale_state_after_roll(st):
    """The device state built at set-up served for ever: a memtable roll
    that is never rebuilt into the snapshot (a step that returns its state
    unchanged)."""
    first = st.device_state()
    st.device_state = lambda: first


def drop_writes(st):
    """A write acknowledged and never applied: the state stays as it was."""
    st.put_batch = lambda keys, values=None: None


class _HalfFetch:
    def __init__(self, fetch):
        self.fetch, self.span = fetch, fetch.span

    def wait(self):
        found, vals = self.fetch.wait()
        half = found.shape[0] // 2
        found, vals = found.copy(), vals.copy()
        found[half:] = False
        vals[half:] = 0
        return found, vals


def half_batch(st):
    """Half of every dispatched batch left unanswered (reported absent)."""
    real = st.resolve_get_async
    st.resolve_get_async = lambda pb: _HalfFetch(real(pb))


def alter_answers(st):
    """One bit of every value flipped where the value log produces it."""
    for sh in st.shards:
        real = sh.vlog.get_batch_np

        def flipped(ptrs, real=real):
            out = np.array(real(ptrs))
            out[:, 0] ^= 1
            return out

        sh.vlog.get_batch_np = flipped


FAULTS = [(c, f) for c in CELLS
          for f in (half_batch, alter_answers, drop_writes,
                    stale_state_after_roll)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_planted_fault_makes_the_run_incorrect(cell, fault):
    out = run_tiny(cell, store_hook=fault)
    assert not out["correct"]
    assert out["check"]["wrong_keys"]["value"] > 0
    if fault is stale_state_after_roll:
        assert out["check"]["wrong_keys_after_roll"]["value"] > 0
