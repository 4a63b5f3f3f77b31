"""Time the set-up spent building the index (``index.build`` span), in s."""


def read(ctx):
    spans = [s["seconds"] for s in ctx["spans"] if s["name"] == "index.build"]
    return sum(spans) if spans else None
