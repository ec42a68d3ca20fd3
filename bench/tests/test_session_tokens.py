"""``session_tokens_per_s`` on hand-made runs: the warm-up's backlog inside
the window, which every token streamed inside the window counted, does not
count; a faster program reads at least as high; a stall inside the window
and a reply cut short read lower, by exactly the tokens they keep out."""
import pytest

from bench.harness import RequestRecord, Run
from bench.spec import reader

T_OPEN, SECONDS = 100.0, 10.0
T_CLOSE = T_OPEN + SECONDS
#: (stream, arrival, replies[turn] -> reply lengths of the turn's requests)
WINDOW = [("window", 102.0, [[8, 4], [6, 6]]),
          ("window", 105.0, [[4, 4], [6, 2]]),
          ("post", T_CLOSE, [[5, 5]])]
WINDOW_TOKENS = 8 + 4 + 6 + 6 + 4 + 4 + 6 + 2

read = reader("session_tokens_per_s")


def _served(stream, arrival, replies, per_token):
    """A session's requests served at ``per_token`` seconds a token: a
    turn's replies stream side by side from its due time, and the next turn
    is due when its longest reply has finished."""
    out, due = [], arrival
    for turn in replies:
        for n in turn:
            out.append(RequestRecord(
                stream=stream, module="m", due=due, prompt_len=8,
                max_tokens=n, finished=True, served=[5] * n,
                token_times=[due + per_token * (i + 1) for i in range(n)],
                routed=None, chunks=[]))
        due += per_token * max(turn)
    return out


def _run(sessions, per_token):
    records = [r for s in sessions for r in _served(*s, per_token)]
    return Run(cell="synthetic", seconds=SECONDS, setup_s=1.0, t_open=T_OPEN,
               t_close=T_CLOSE, drain_end=T_CLOSE,
               window=[r for r in records if T_OPEN <= r.due < T_CLOSE],
               requests=records, stats_open={}, stats_close={}, pages_peak=0,
               pool_pages=1, compiles_window=0, shapes=None, peaks=None)


def _streamed_in_window(run):
    """The retired ``output_tokens_per_s``: every token streamed inside the
    window, by any request, over the window's seconds."""
    n = sum(1 for r in run.requests for t in r.token_times
            if run.t_open <= t < run.t_close)
    return n / (run.t_close - run.t_open)


@pytest.mark.parametrize("warmup_arrival", [95.0, 97.0])
def test_warmup_backlog_inside_the_window_does_not_count(warmup_arrival):
    """One window served at one speed, after a warm-up session that ends
    before the window opens or streams into it: the old reader told the two
    apart, this one reads them alike."""
    done = _run([("warmup", 80.0, [[10, 6], [12, 8]])] + WINDOW, 0.1)
    spilled = _run([("warmup", warmup_arrival, [[10, 6], [12, 8]])] + WINDOW,
                   0.5)
    spilled.requests = [r for r in spilled.requests if r.stream == "warmup"]
    spilled.requests += [r for r in done.requests if r.stream != "warmup"]
    assert any(r.stream == "warmup" and r.token_times[-1] >= T_OPEN
               for r in spilled.requests)
    assert _streamed_in_window(spilled) > _streamed_in_window(done)
    assert read(spilled) == read(done) == WINDOW_TOKENS / SECONDS


def test_faster_program_reads_at_least_as_high():
    """The slow program's warm-up backlog made the old reader read it higher
    than the fast one; this one reads the fast program higher."""
    warmup = ("warmup", 95.0, [[10, 6], [12, 8]])
    slow, fast = _run([warmup] + WINDOW, 0.5), _run([warmup] + WINDOW, 0.1)
    assert _streamed_in_window(slow) > _streamed_in_window(fast)
    assert read(fast) == WINDOW_TOKENS / SECONDS
    # the slow window sessions' last turns end after the window has closed
    assert any(r.stream == "window" and r.token_times[-1] >= T_CLOSE
               for r in slow.requests)
    assert read(slow) < read(fast)


def test_stall_inside_the_window_reads_lower():
    """A 4.5 s stall at 105 s pushes every later token of the window's
    sessions back: those that then fall past the close no longer count."""
    run = _run(WINDOW, 0.1)
    stall = 4.5
    for r in run.requests:
        r.token_times = [t + stall if t >= 105.0 else t for t in r.token_times]
    lost = sum(1 for r in run.requests if r.stream == "window"
               for t in r.token_times if t >= T_CLOSE)
    assert lost > 0
    assert read(run) == (WINDOW_TOKENS - lost) / SECONDS


def test_reply_cut_short_reads_lower_by_its_missing_tokens():
    run = _run(WINDOW, 0.1)
    r = next(r for r in run.requests if r.stream == "window"
             and r.max_tokens == 6)
    missing = 4
    del r.served[-missing:], r.token_times[-missing:]
    r.finished = False
    assert read(run) == (WINDOW_TOKENS - missing) / SECONDS
