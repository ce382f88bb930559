"""The trainer's one dispatch loop (``train/trainer.py::_dispatch_loop``)
over the four sites' settings, with no device program: a feed of five
items, a recording step, heartbeat, fault plan and snapshot.  What whole
training runs cover elsewhere (tests/test_resilience.py,
test_device_cache.py, test_hostfed_preemption.py) is held here per item."""

import pytest

from fast_autoaugment_tpu.core.resilience import (
    PreemptedError,
    clear_preemption,
    request_preemption,
)
from fast_autoaugment_tpu.core.watchdog import resolve_watchdog
from fast_autoaugment_tpu.train.trainer import (
    _CachedFeed,
    _dispatch_loop,
    _HostFeed,
    _StackedHostFeed,
)

ITEMS, STEP0 = 5, 100


class _Site:
    """One site's settings and a record of everything the loop did."""

    def __init__(self, label, cached, stops, pos=0, stop_at_beat=None):
        self.events, self.beats = [], 0
        self.stops, self.stop_at_beat = stops, stop_at_beat
        if cached:
            self.feed = _CachedFeed(label, self, self._chunk_args, ITEMS, 1, pos,
                                    fi=self)
        else:
            feed = _StackedHostFeed if label.startswith("stacked") else _HostFeed
            self.feed = feed(label, self._step, lambda: iter(range(pos, ITEMS)),
                             lambda batch: (batch,), ITEMS, pos, fi=self)

    # the programs a cached feed asks for, and what it places a chunk
    def multi_step(self, n):
        return self._step

    def _chunk_args(self, pos, n):
        return (pos,)

    def _step(self, state, item):
        self.events.append(("dispatch", item))
        return state + 1, {"loss": float(item + 1)}

    # heartbeat, fault plan, snapshot, stop
    def heartbeat(self):
        self.events.append(("beat",))
        self.beats += 1
        if self.beats == self.stop_at_beat:
            request_preemption()

    def dispatch_delay(self, step):
        self.events.append(("seam", step))

    def maybe_signal(self, step):
        self.events.append(("signal", step))

    def snapshot(self, state, pos, sums):
        self.events.append(("snapshot", state, pos, dict(sums)))

    def preempted(self, pos, total):
        return PreemptedError(f"stopped at {pos}/{total}")

    def run(self, every=0):
        stops = (dict(snapshot=self.snapshot, preempted=self.preempted)
                 if self.stops else {})
        return _dispatch_loop(
            self.feed, 0, wd=resolve_watchdog("off"), heartbeat=self.heartbeat,
            step0=STEP0, every=every, **stops)


SITES = {  # label: (cached feed, takes a mid-epoch snapshot and stop)
    "train_dispatch": (True, True),
    "train_step": (False, True),
    "stacked_dispatch": (True, True),
    "stacked_step": (False, False),
}
STOPPING = [label for label, (_, stops) in SITES.items() if stops]


@pytest.fixture(autouse=True)
def _no_stop_left_over():
    clear_preemption()
    yield
    clear_preemption()


@pytest.mark.parametrize("label", SITES)
def test_each_item_is_dispatch_then_heartbeat_then_signal(label):
    site = _Site(label, *SITES[label])
    assert site.run() == ITEMS  # the state after the epoch's last dispatch
    per_item = [[("seam", STEP0 + i + 1), ("dispatch", i), ("beat",),
                 ("signal", STEP0 + i + 1)] for i in range(ITEMS)]
    assert site.events == [e for item in per_item for e in item]
    assert site.feed.sums() == {"loss": 15.0}


@pytest.mark.parametrize("label", SITES)
def test_a_stop_at_item_three_snapshots_once_and_raises(label):
    site = _Site(label, *SITES[label], stop_at_beat=3)
    if not site.stops:
        # no mid-epoch snapshot at this site: the stop waits for the boundary
        assert site.run() == ITEMS
        assert not [e for e in site.events if e[0] == "snapshot"]
        return
    with pytest.raises(PreemptedError, match="stopped at 3/5"):
        site.run()
    assert site.events[-4:] == [
        ("dispatch", 2), ("beat",), ("signal", STEP0 + 3),
        ("snapshot", 3, 3, {"loss": 6.0})]
    assert [e[0] for e in site.events].count("snapshot") == 1
    assert [e[0] for e in site.events].count("dispatch") == 3


@pytest.mark.parametrize("label", STOPPING)
def test_a_periodic_snapshot_every_two_and_none_at_the_last_item(label):
    site = _Site(label, *SITES[label])
    assert site.run(every=2) == ITEMS
    assert [e[1:] for e in site.events if e[0] == "snapshot"] == [
        (2, 2, {"loss": 3.0}), (4, 4, {"loss": 10.0})]
    # the snapshots' sums went on: the epoch's are those of the unbroken loop
    assert site.feed.sums() == {"loss": 15.0}


@pytest.mark.parametrize("label,positions", [
    ("train_dispatch", [3]),  # dispatches number from where the process entered
    ("train_step", [2, 4]),   # batches number from the epoch's start
])
def test_a_resumed_epoch_numbers_its_dispatches_as_the_site_did(label, positions):
    site = _Site(label, *SITES[label], pos=1)
    assert site.run(every=2) == ITEMS - 1
    assert [e[1] for e in site.events if e[0] == "dispatch"] == [1, 2, 3, 4]
    assert [e[2] for e in site.events if e[0] == "snapshot"] == positions


def test_the_entry_points_run_in_a_stack_chunk_of_their_own():
    """``core/compilecache.py::roomy``: the trainer's calls, the eager
    set-up with them, do not depend on where the interpreter's 16 KiB
    frame-stack chunks end under the caller."""
    from fast_autoaugment_tpu.train.trainer import (
        train_and_eval,
        train_folds_stacked,
    )
    for entry in (train_and_eval, train_folds_stacked):
        assert entry.__code__.co_stacksize >= 1 << 15
