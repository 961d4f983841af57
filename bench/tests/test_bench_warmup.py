"""The warm-up's requests run both step widths at every live bucket the
cell's traffic can reach, so nothing the window can run is left to
compile."""
import pytest

from bench import harness as H
from bench import spec as SP


def shapes_run(p, max_seq, block, chunk):
    """(width, bucket) of every step of one warm-up prompt of length ``p``
    as the engine plans them: its prefill chunks alone, one decode step
    alone, and one step beside a newly admitted prompt."""
    bps = max_seq // block
    out, pos = set(), 0
    while pos < p:
        width = chunk if p - pos > 1 else 1
        out.add((width, H.live_bucket(pos + 1, block, bps)))
        pos += min(width, p - pos)
    out.add((1, H.live_bucket(p + 1, block, bps)))
    out.add((chunk, H.live_bucket(p + 2, block, bps)))
    return out


@pytest.mark.parametrize("name", [w["name"] for w in
                                  SP.benchmark()["workloads"]])
def test_every_width_and_bucket_is_warmed(name):
    mix = SP.load_cell(name).traffic
    e = mix["engine"]
    max_seq, block, chunk = e["max_seq"], e["block_size"], e["prefill_chunk"]
    bps = max_seq // block
    # a running request sits at 0 or a multiple of the chunk while its
    # prompt is fed, and past its prompt while it decodes
    reach = {1} | set(range(chunk + 1, max_seq + 1, chunk)) | \
        set(range(mix["prompt"]["min"] + 1, max_seq + 1))
    buckets = {H.live_bucket(hw, block, bps) for hw in reach}
    run = set()
    for p in H.warmup_prompts(max_seq, block, chunk, max_seq):
        run |= shapes_run(p, max_seq, block, chunk)
    assert run >= {(w, b) for w in (1, chunk) for b in buckets}
