"""The generator: every seed gets the same work at the same times, its own
token ids; lengths lie on the file's grid, so the step shapes the set-up
makes ready cover every chunk the traffic can cut."""
import glob
import os

import pytest

from bench import model as bmodel
from bench import traffic, warm

HERE = os.path.dirname(__file__)
MIXES = sorted(glob.glob(os.path.join(HERE, "..", "traffic", "*.json")))
MODULES = ["agent_a", "agent_b"]


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_same_work_for_every_seed(path):
    t = bmodel.load(path)
    a = traffic.sessions(t, MODULES, 151936, 2**31 + 1, "window", 51)
    b = traffic.sessions(t, MODULES, 151936, 2**33 + 9, "window", 51)
    assert len(a) == len(b) == round(t["rate_sessions_per_s"] * 51)
    for x, y in zip(a, b):
        assert x.arrival == y.arrival and 0 <= x.arrival < 51
        assert x.replies == y.replies
        assert len(x.document) == len(y.document)
        assert x.document != y.document
    assert [s.arrival for s in a] == sorted(s.arrival for s in a)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_prompts_and_chunks_on_the_grid(path):
    t = bmodel.load(path)
    q = t["length_quantum"]
    sys_ = traffic.system_prompt(t, 151936, 5)
    assert len(sys_) == t["system_prompt_tokens"]
    _, chunks = warm.shapes(t, {"chunk_size": 256}, 2, 16)
    sizes = {s for _, s, _ in chunks}
    for stream in ("warmup", "window", "post"):
        for s in traffic.sessions(t, MODULES, 151936, 7, stream, 51):
            for turn in range(s.turns):
                for m in MODULES:
                    n = len(s.prompt(sys_, turn, m))
                    assert n % q == 0
                    # every chunk is whole chunks of 256 and one remainder
                    assert n % 256 == 0 or n % 256 in sizes
                    lo, hi = t["reply_tokens"]
                    assert lo <= s.replies[turn][m] <= hi
