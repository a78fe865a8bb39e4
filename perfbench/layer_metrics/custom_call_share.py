"""kernels. Share of the device's busy time spent in Mosaic (Pallas)
custom calls, over the traced window."""


def read(ctx):
    red = ctx.get("trace")
    if not red or not red["busy_s"]:
        return None
    return 100.0 * red["custom_call_s"] / red["busy_s"]
