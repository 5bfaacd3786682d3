"""end to end: rows of the cell's fact table scanned by every query
completed in the window, over the window's elapsed seconds (first query
started to last query returned). All the work over all the time."""


def read(run):
    w = run["window"]
    return w["fact_rows"] * w["queries"] / w["elapsed_s"]
