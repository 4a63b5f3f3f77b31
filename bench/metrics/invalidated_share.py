"""Mean share of the vertices whose cached answers one write invalidated,
in %: ``owners`` (the affected owners a write invalidated, in the plan
group with the most) of the ``service.update`` spans that start inside the
window, over the graph's vertices.  Silent where the program stamps no
``owners``."""


def read(ctx):
    lo, hi = ctx["window"]
    owners = [s["args"]["owners"] for s in ctx["spans"]
              if s["name"] == "service.update" and lo <= s["start"] <= hi
              and "owners" in s["args"]]
    if not owners:
        return None
    n = ctx["config"]["graph"]["n"]
    return 100.0 * sum(owners) / len(owners) / n
