"""HBM bytes the fleet triage operation needs for one scheduler tick.

Counted from what the operation reads and writes for the tick's real
(query, edge) rows and items, not from the padded slab a kernel lays
them out in nor from how it computes the prefix sum: per item a float32
confidence in and an int32 route and slot out; per row a one-byte
"has items" flag in, the row's float32 [alpha, beta] after the tick out
and an int32 escalation count out.  The comparisons are a few operations
per item, so the bound is HBM.
"""
ITEM_BYTES = 4 + 4 + 4
ROW_BYTES = 1 + 8 + 4


def cost(rows: int, items: int) -> float:
    return float(items * ITEM_BYTES + rows * ROW_BYTES)
