"""The operation and byte counts against hand counts at one small shape."""
from bench import flops
from bench.model import family

QWEN3 = family({"model_type": "qwen3"})
# d=8, d_ff=16, 2 layers, 4 query heads and 2 kv heads of width 2, vocab 10
S = QWEN3.Shapes(d=8, d_ff=16, layers=2, hq=4, hkv=2, head_dim=2, vocab=10,
                 tied=True, rope_theta=1e4, eps=1e-6)


def test_paged_decode_by_hand():
    # keys 3 + 5 = 8; per key q.k and p.v: 2 * 2 * (4 heads * 2) = 32
    f, b = flops.paged_decode(S, [3, 5])
    assert f == 8 * 32
    # K and V rows: 8 keys * 2 * (2 * 2) wide * 2 bytes = 128;
    # q and o of 2 sequences: 2 * (2 * 8 wide * 2 bytes) = 64
    assert b == 128 + 64


def test_flash_prefill_paged_by_hand():
    # a chunk of 3 queries at 4, 5, 6 sees 5 + 6 + 7 = 18 keys; a chunk of
    # 2 at 0, 1 sees 1 + 2 = 3
    f, b = flops.flash_prefill_paged(S, [(4, 3), (0, 2)])
    assert f == (18 + 3) * 32
    # K/V rows read once per chunk: (7 + 2) * 2 * 4 * 2 = 144;
    # q and o: (3 + 2) * 2 * 8 * 2 = 160
    assert b == 144 + 160


def test_steps_by_hand():
    # per token and layer: q 8*8, k and v 2 * 8*4, o 8*8, mlp 3 * 8*16,
    # times 2 for multiply-add: 2 * (64 + 64 + 64 + 384) = 1152
    layer = 1152
    head = 2 * 8 * 10
    assert QWEN3.decode_step(S, [3]) == 2 * layer + head + 2 * 3 * 32
    assert QWEN3.chunk_step(S, [(4, 3)]) == 2 * (3 * layer + 18 * 32)
    # the kernels' calls, once per layer
    assert QWEN3.paged_decode(S, [3, 5]) == (2 * 8 * 32, 2 * (128 + 64))
    assert QWEN3.flash_prefill_paged(S, [(4, 3), (0, 2)]) == (
        2 * 21 * 32, 2 * (144 + 160))


def test_shares_never_pass_the_peak():
    """A kernel that did exactly the counted work at the chip's peak reads
    100%: the counts are what the work needs, not more."""
    from types import SimpleNamespace

    from bench.measure import kernel_roofline
    from bench.peaks import peaks
    trace = SimpleNamespace(t0_ns=0, t1_ns=10**9, rec={
        "ops": [["%paged_decode.1 = x", 0, 10**6, 0, ""]]})
    run = SimpleNamespace(trace=trace, peaks=peaks("TPU v5 lite"))
    f = run.peaks["bf16_flops"] * 1e-3          # one ms of peak work
    assert abs(kernel_roofline(run, "paged_decode", (f, 0)) - 100) < 1e-9
    b = run.peaks["hbm_bytes_per_s"] * 1e-3
    assert abs(kernel_roofline(run, "paged_decode", (1, b)) - 100) < 1e-9
