"""Time the set-up spent laying out the device plan and making it resident
(``plan.upload`` span, which ends in ``block_until_ready``), in s."""


def read(ctx):
    spans = [s["seconds"] for s in ctx["spans"] if s["name"] == "plan.upload"]
    return sum(spans) if spans else None
