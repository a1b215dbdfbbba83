"""``tests/utils_mp.py:World``, the ranks that stay up behind a module's
``*_world`` fixture: they start once and serve body after body; a body
that fails, or a rank that dies, fails ITS test with the rank's own
traceback and ends that world, and the next test gets a new one."""

import os

import pytest

from tests import utils_mp

_STARTS = []


def init():
    """This module in a frontend's place: ``utils_mp.worlds`` asks a
    frontend for ``init`` and ``shutdown`` and nothing else."""
    _STARTS.append(os.getpid())


def shutdown():
    pass


def _starts_and_rank(rank, size):
    return len(_STARTS), rank, os.getpid()


def _rank_one_raises(rank, size):
    if rank == 1:
        raise ValueError("planted in rank one")
    return "ok"


def _rank_zero_dies(rank, size):
    if rank == 0:
        os._exit(3)
    return "ok"


def test_ranks_start_once_and_serve_body_after_body():
    with utils_mp.worlds(__name__) as world:
        first = world(2).run(_starts_and_rank)
        again = world(2).run(_starts_and_rank)
        ranks = world(2)
    assert [r[:2] for r in first] == [(1, 0), (1, 1)]   # by rank
    assert again == first                  # the same processes, one start
    assert first[0][2] != first[1][2] != os.getpid()
    assert not ranks.alive                 # left: shut down and joined
    with pytest.raises(AssertionError, match="has ended"):
        ranks.run(_starts_and_rank)


@pytest.mark.parametrize("body, said", [
    (_rank_one_raises, r"\[rank 1\][\s\S]*ValueError: planted in rank one"),
    (_rank_zero_dies, r"\[rank 0\] died")], ids=["raises", "dies"])
def test_a_failing_body_fails_alone_and_the_next_gets_a_new_world(body,
                                                                  said):
    with utils_mp.worlds(__name__) as world:
        before = world(2).run(_starts_and_rank)
        failed = world(2)
        with pytest.raises(AssertionError, match=said):
            failed.run(body, timeout=60)
        assert not failed.alive and world(2) is not failed
        after = world(2).run(_starts_and_rank)
    assert [r[:2] for r in after] == [(1, 0), (1, 1)]
    assert not {r[2] for r in before} & {r[2] for r in after}
