"""frame program. The least time the chip could take for the traced
frames' work (weights once a step plus KV bytes over peak bytes/s, or
useful FLOPs over peak FLOP/s, whichever is larger: ``work.py``) over the
device's busy time in them. The driver's log line says which bound."""


def read(ctx):
    span = ctx.get("span")
    if not span or not span["busy_s"]:
        return None
    return 100.0 * span["floor_s"] / span["busy_s"]
