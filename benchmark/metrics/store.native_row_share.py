"""Share of the stored rows that the sqlite store wrote through its native
bulk insert, in %: the counter store_rows_native over store_rows_written.
A program without the native path counts no store_rows_native: no
reading."""

NATIVE = "store_rows_native"
WRITTEN = "store_rows_written"


def read(ctx):
    c = ctx["snapshot"].get("counters", {})
    if NATIVE not in c or not c.get(WRITTEN):
        return None
    return 100.0 * c[NATIVE] / c[WRITTEN]
