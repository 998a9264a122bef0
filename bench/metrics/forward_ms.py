"""Vision backend: mean host time of one batched forward
(``M3ViTServer.infer``, which ends in a host array), from the harness's
span around it."""


def read(ctx):
    t = ctx.spans.seconds.get("infer") if ctx.spans else None
    return 1e3 * sum(t) / len(t) if t else None
