"""Least HBM traffic of the (contig, mer) walk-table builds of one assembly.

Counted from shapes alone, so it is the same whatever does the work (probe
rounds, compaction, a kernel).  Per build and per rung of mer size m:

  * every inserted lane read once: each of the R * (L - m + 1) read windows
    gives a key of two 32-bit words (the contig tag rides in its spare
    bits) and its two extension bases, one byte each;
  * every table slot written once: two 32-bit key words and a used flag;
  * every histogram row written once: left and right, 4 int32 counts each.

An assembly builds the tables once per k round (local assembly) and once
more for gap closing; `shapes["walk_table_builds"]` lists their rungs.
"""

LANE_BYTES = 4 + 4 + 1 + 1
SLOT_BYTES = 4 + 4 + 1 + 2 * 4 * 4


def hbm_bytes(shapes: dict) -> int:
    R, L = shapes["reads"]
    cap = shapes["walk_capacity"]
    total = 0
    for rungs in shapes["walk_table_builds"]:
        for m in rungs:
            total += R * max(L - m + 1, 0) * LANE_BYTES + cap * SLOT_BYTES
    return total
