"""Tail arithmetic: stalls injected into a token-time schedule move the
tails of the time to first token and of the gaps between tokens."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import stats  # noqa: E402


def schedule(stall_every=0, stall=0.0, n_req=200, step=0.02, toks=8):
    """An engine stepping every ``step`` s emits one token per active
    request per step; every ``stall_every``-th step (0: none) takes
    ``stall`` s longer. Requests are due every 0.05 s, and each gets its
    first token at the first step ending after its due time."""
    ends, t = [], 0.0
    for k in range(1, 20000):
        t += step + (stall if stall_every and k % stall_every == 0 else 0)
        ends.append(t)
    reqs = []
    j = 0
    for i in range(n_req):
        due = 0.05 * i
        while ends[j] <= due:
            j += 1
        times = ends[j:j + toks]
        reqs.append({"due": due, "t_first": times[0], "tok_times": times})
    return reqs


def test_nearest_rank():
    assert stats.nearest_rank([5, 1, 3, 2, 4], 0.5) == 3
    assert stats.nearest_rank(range(1, 101), 0.9) == 90
    assert stats.nearest_rank(range(1, 101), 0.95) == 95
    assert stats.nearest_rank([7], 0.99) == 7


def test_stalls_move_ttft_and_itl_tails():
    base = schedule()
    hit = schedule(stall_every=4, stall=0.3)
    t_end = 1e9
    assert stats.nearest_rank(stats.itl_ms(base), 0.95) < 21
    assert stats.nearest_rank(stats.itl_ms(hit), 0.95) > 300
    assert stats.nearest_rank(stats.ttft_ms(base, t_end), 0.90) < 21
    assert stats.nearest_rank(stats.ttft_ms(hit, t_end), 0.90) > 100


def test_unserved_request_counts_its_wait():
    reqs = schedule(n_req=10)
    reqs[3]["t_first"] = None
    ttft = stats.ttft_ms(reqs, t_end=60.0)
    assert ttft[3] == (60.0 - reqs[3]["due"]) * 1e3
