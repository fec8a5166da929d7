"""The work functions, pinned to arithmetic done by hand for one shape
each, and the pair count to a brute-force walk of the partition rule."""
import json
import os

import numpy as np

from bench import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def brute_pairs(L, nr):
    """Attended units of every query: each key at level 0, each coarse
    group at a higher level, by the smallest level at which the two
    blocks are neighbours."""
    total = 0
    for i in range(L):
        units = set()
        for j in range(i + 1):
            lvl = 0
            while abs(i // (nr << lvl) - j // (nr << lvl)) > 1:
                lvl += 1
            units.add((lvl, j >> lvl))
        total += len(units)
    return total


def test_pairs_match_partition_rule():
    for L, nr in ((16, 16), (48, 16), (100, 4), (256, 8)):
        assert work.h1d_pairs(L, nr) == brute_pairs(L, nr)


def test_pairs_by_hand():
    # L=48, nr=16: level 0 gives 3*(1+..+16) + 16*32 = 920; level 1
    # (span 32) gives the 16 queries 32..47, first half, 8 keys each
    assert work.h1d_pairs(48, 16) == 920 + 128
    # one query at 30000: 17 at level 0, then 16/16/8/8/16/8/16/8/16/16
    assert int(work.h1d_pairs_at(30000, 16)) == 17 + 128


def test_train_step_by_hand():
    c = cfg("h1d-lm-144m")
    # per layer 1024*1024 + 2*1024*1024 + 1024*1024 + 3*1024*4096, six
    # layers, plus the tied head 1024*32768
    assert work.matmul_params(c) == 6 * 16_777_216 + 33_554_432
    # L=4096, nr=16: level 0 256*136 + 16*4080 = 100096; levels 1..7
    # 12 * sum(4096 - 16*2**l) = 12 * 24608
    pairs = 100_096 + 12 * 24_608
    assert work.h1d_pairs(4096, 16) == pairs
    fwd = 4 * 128 * 8 * 2 * pairs
    t = work.train_step(c, 2, 4096)
    assert t["flops"] == 6 * 134_217_728 * 8192 + 6 * 3 * fwd
    assert t["h1d_flops"] == 6 * 3 * fwd
    # f32: forward reads q, k, v and writes out (4 tensors of 8*128) plus
    # one f32 statistic per row and head; backward reads q, k, v, out,
    # d_out and the statistic and writes dq, dk, dv
    fb = 8192 * (128 * 32 * 4 + 4 * 8)
    bb = 8192 * (128 * 64 * 4 + 4 * 8)
    assert t["h1d_bytes"] == 6 * (fb + bb)


def test_prefill_by_hand():
    c = cfg("yi-6b")
    p = work.prefill(c, [16, 48])
    n = 8 * 4 * 128 * 32 * (136 + 1048)
    assert p["h1d_flops"] == n
    assert p["flops"] == 2 * work.matmul_params(c) * 64 + n


def test_decode_tick_by_hand():
    c = cfg("yi-6b")
    # per layer 4096*4096*2 + 2*4096*512 + 3*4096*11008; head 4096*64000
    assert work.matmul_params(c) == 8 * 173_015_040 + 262_144_000
    t = work.decode_tick(c, [30000] * 4)
    attn = 4 * 8 * 4 * 128 * 32 * 145
    assert t["h1d_flops"] == attn
    assert t["flops"] == 2 * 1_646_264_320 * 4 + attn
    # bf16 weights once, 17 norm gains and 4 embedding rows of 4096
    weights_read = (1_646_264_320 + 21 * 4096) * 2
    # per session and layer: 4 KV heads * 128 * (K and V) * 2 bytes over
    # 145 attended rows and 2 rows per level (1875 blocks: 11 bits, +1),
    # plus q read and output written (2 * 32 * 128 * 2 bytes)
    cache = 4 * 8 * (4 * 128 * 2 * 2 * (145 + 2 * 12) + 2 * 32 * 128 * 2)
    assert t["bytes"] == weights_read + cache
    assert work.roofline_seconds(t["flops"], t["bytes"],
                                 {"bf16_flops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9})[1] == "bytes"


def test_pairs_at_vectorised():
    i = np.arange(300)
    assert list(work.h1d_pairs_at(i, 8)) == [int(work.h1d_pairs_at(x, 8))
                                             for x in i]
