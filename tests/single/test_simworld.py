"""Simulated large-world harness (docs/scale.md): thread-per-rank
controllers over socketpairs run the REAL negotiation protocol — flat
star and HOROVOD_CONTROL_TREE tree gather — plus the real ring
allreduce, in one process. Pins:

- negotiation + allreduce completes and verifies at both small and
  large worlds, both gather modes (256 ranks = the north-star size);
- the per-phase control-plane profile (gather/broadcast/rendezvous
  histograms) comes out of every run — the scaling-curve plumbing;
- the tree gather beats the flat star's GROWTH: sub-linear vs the
  sequential baseline between 32 and 128 ranks, by the count of frames
  the coordinator receives a gather (no wall-clock);
- an injected kill surfaces typed PeerFailure attribution naming the
  dead rank on the survivors, flat and tree.
"""

import pytest

from horovod_tpu.common.basics import HorovodBasics

pytestmark = pytest.mark.quick

_b = HorovodBasics()


def _run(ranks, **kw):
    return _b.simworld_run(ranks, **kw)


def test_small_world_flat_and_tree_complete_and_verify():
    for fanout in (0, 2):
        rep = _run(8, tree_fanout=fanout, elems=512, rounds=3)
        assert rep["rc"] == 0 and rep["allreduce_ok"], rep
        assert rep["round_us"]["count"] == 3, rep
        for phase in ("rendezvous", "gather", "broadcast"):
            assert rep["phases"][phase]["count"] > 0, (fanout, phase)
        # Steady state: rounds 2+ ride the response-cache bit path —
        # the gather still records once per cycle.
        assert rep["phases"]["gather"]["count"] == 3, rep


@pytest.mark.slow
def test_256_rank_world_completes_negotiation_and_allreduce():
    # The acceptance world size (ISSUE r16 / ROADMAP item 5). ~10 s.
    for fanout in (0, 8):
        rep = _run(256, tree_fanout=fanout, elems=64, rounds=2)
        assert rep["rc"] == 0 and rep["allreduce_ok"], (fanout, rep)
        assert rep["data_mesh"] == "ring", rep  # fd-budget topology


def test_tree_gather_grows_sublinearly_vs_flat():
    """The tentpole claim, pinned at CI-safe sizes and by COUNT: the
    frames the coordinator receives one after the other in a gather
    (``gather_frames``, counted where it receives them). Growing the
    world 32 -> 128 (4x) grows the flat star's 31 -> 127; the tree's
    stay its fanout. The gather's latency histogram is still read, as
    plumbing; no wall-clock ratio is asserted (under six xdist workers
    the flat star's 8 ms gathers and the tree's 4 ms wander by more
    than their difference)."""

    def frames_a_gather(ranks, fanout):
        rep = _run(ranks, tree_fanout=fanout, elems=64, rounds=6)
        assert rep["rc"] == 0, rep
        h = rep["phases"]["gather"]
        assert h["count"] == 6 and h["sum_us"] > 0, rep
        assert rep["gather_frames"] % h["count"] == 0, rep
        return rep["gather_frames"] // h["count"]

    flat = [frames_a_gather(n, 0) for n in (32, 128)]
    tree = [frames_a_gather(n, 8) for n in (32, 128)]
    assert flat == [31, 127] and tree == [8, 8], (flat, tree)
    flat_growth, tree_growth = flat[1] / flat[0], tree[1] / tree[0]
    assert tree_growth < flat_growth, (
        f"tree gather grew {tree_growth:.2f}x from 32->128 ranks vs "
        f"flat {flat_growth:.2f}x — not sub-linear vs the baseline")


def test_injected_kill_names_dead_rank_flat_and_tree():
    for fanout in (0, 8):
        rep = _run(64, tree_fanout=fanout, elems=64, rounds=3,
                   kill_rank=37, kill_round=1)
        assert rep["rc"] == 0, rep
        fault = rep["fault"]
        assert fault["typed_faults"] == 63, (fanout, fault)
        assert fault["named_rank"] == 37, (fanout, fault)


def test_refuses_to_run_next_to_live_core_and_bad_args():
    # Bad arguments are rejected outright (rc -1 -> RuntimeError).
    with pytest.raises(RuntimeError, match="bad arguments"):
        _run(1)
    with pytest.raises(RuntimeError, match="bad arguments"):
        _run(8, kill_rank=3)  # kill without a kill_round
