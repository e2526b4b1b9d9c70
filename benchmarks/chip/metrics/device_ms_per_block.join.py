"""Device busy time of the traced window per block of 4,096 query rows.

The block is a fixed yardstick counted from the rows the window answered,
not the program's own R block or dispatch count: a change of the
program's block size does not move this metric by itself."""

ROWS_PER_BLOCK = 4096


def read(run):
    if not run["rows"]:
        return None
    return run["trace"]["busy_s"] / (run["rows"] / ROWS_PER_BLOCK) * 1e3
