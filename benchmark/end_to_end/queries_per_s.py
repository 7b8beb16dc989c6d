"""All queries completed in the window over the window's seconds (the
window ends with its last query, so no partial query is counted or cut)."""


def read(run: dict):
    return len(run["queries"]) / run["window_s"] if run["queries"] else None
