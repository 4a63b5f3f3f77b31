"""Peaks of each device kind, and the least work of the measured calls.

A roofline share is the least time the chip could take for a call's work,
the larger of its operations over the peak rate and its bytes over the
peak bandwidth, divided by the time the call took.  The work counted here
is the least the call must do, worked out from the sizes of the index it
serves (real members, not padding), so that a share reads the same
whatever implements the call.  A device kind missing from ``PEAKS`` is an
error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flop_per_s": 197e12,
        "bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "(bf16), 16 GB HBM at 819 GB/s per chip",
    },
}

WORD = 4  # bytes of an int32 index or a float32 value


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_time_s(flops: float, nbytes: float, device_kind: str) -> float:
    p = peaks(device_kind)
    return max(flops / p["flop_per_s"], nbytes / p["bytes_per_s"])


def segment_sum_work(rows: int, channels: int, segments: int):
    """``(flops, bytes)`` of a segment sum of ``rows`` gathered rows of
    ``channels`` values into ``segments`` outputs: one add per value; each
    value and segment id read once, each output written once."""
    flops = rows * channels
    nbytes = WORD * (rows * channels + rows + segments * channels)
    return flops, nbytes


def launch_bytes(index_words: int, members: int, n: int, rows: int,
                 channels: int) -> int:
    """Bytes an ``[rows, n]`` explicit-values launch cannot avoid: the
    index arrays read once (``index_words`` int32 words), one value per
    window member per row, and the ``channels`` outputs of every vertex
    per row."""
    return WORD * (index_words + rows * members + rows * n * channels)


def plan_counts(index) -> dict:
    """Sizes of the served index that the work functions need: for a
    DBIndex (2-pass) its block members, blocks and owner links, for an
    I-Index its window-difference members and parent forest."""
    if hasattr(index, "block_members"):
        members = int(index.block_members.shape[0])
        links = int(index.link_block.shape[0])
        return {"kind": "dbindex", "n": int(index.n), "members": members,
                "blocks": int(index.num_blocks), "links": links,
                "index_words": members + links}
    members = int(index.wd_members.shape[0])
    return {"kind": "iindex", "n": int(index.n), "members": members,
            "index_words": members + 2 * int(index.n)}
