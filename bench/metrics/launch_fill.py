"""Share of the rows of the batched launches begun inside the window that
carried a request (``launch`` spans: rows and filled rows), in %.
Launches that drain the last requests after the window's cut-off are left
out, so the share reads the front end's batching, not how the run ends."""


def read(ctx):
    lo, cut = ctx["window"][0], ctx["cut"]
    spans = [s["args"] for s in ctx["spans"]
             if s["name"] == "launch" and lo <= s["start"] <= cut]
    rows = sum(a["rows"] for a in spans)
    return 100.0 * sum(a["filled"] for a in spans) / rows if rows else None
