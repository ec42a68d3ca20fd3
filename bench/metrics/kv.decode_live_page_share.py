"""Share (%) of the block-table pages handed to ``paged_decode`` in the
window that its rows attend to: Δdecode_live_pages ÷ Δdecode_table_pages,
from the engine's counters. The rest is what rounding a decode table's width
up to its bucket costs, plus the padding rows of the lane grid."""


def read(run):
    a, b = run.stats_open, run.stats_close
    if b.get("decode_table_pages") is None:
        return None
    table = b["decode_table_pages"] - a["decode_table_pages"]
    if table <= 0:
        return None
    return 100.0 * (b["decode_live_pages"] - a["decode_live_pages"]) / table
