"""decode_page_fill (model step): share of the decode block tables that
holds KV.  The pages holding each fed row's valid KV, through its new
token (``EngineStats.decode_kv_pages``), over the pages of the tables the
decode steps passed, ``max_batch`` rows by the table's width
(``decode_table_pages``).  The paged attention kernel reads only the
former; the rest is the price of 32 static rows at the widest table.  A
program without these counters reads nothing."""


def read(run):
    kv = run.stats.get("decode_kv_pages")
    table = run.stats.get("decode_table_pages")
    if kv is None or not table:
        return None
    return 100.0 * kv / table
