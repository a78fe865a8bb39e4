"""kernels. The narrow step's paged attention against its roofline where a
narrow frame is TWO blocks wide (``serve_frame/w<2L>``: a row forwards the
block a step commits and, behind it, the next, which the same step begins
to denoise; a row that only denoises has the second L dead): the least time
for the K and V of the positions its rows read (``kv_positions_read`` x
layers x 2 x KV heads x head_dim x 2 B at the HBM's rate) or the query x key
pairs they scored (``attn_pairs`` x layers x heads x head_dim x 4 FLOPs at
the bf16 peak), whichever takes longer, over the device time of
``paged_attn_c<2L>``, the by-head paged kernel at 2 L query positions a row.
The frames and the floor are ``bd_block_attn_roofline``'s
(``work_bd.attention_floor_s``) at the other width: a program whose narrow
frame is a block wide has neither such frames nor that kernel and leaves
this out, as the fused step leaves that one out."""

import re

from perfbench import scope_reduce, trace_reduce, work_bd


def narrow_work(trace, width):
    """(KV positions read, query x key pairs) of ONE layer over the traced
    whole frames ``width`` wide, or None where the trace has none."""
    window = trace_reduce.find_span(trace, scope_reduce.WINDOW_SPAN)
    frames = scope_reduce.frames_with_work(trace, *window) if window else []
    narrow = [w for *_, w in frames if w["width"] == width
              and "kv_positions_read" in w and "attn_pairs" in w]
    if not narrow:
        return None
    return (sum(w["kv_positions_read"] for w in narrow),
            sum(w["attn_pairs"] for w in narrow))


def read(ctx):
    if not work_bd.for_ctx(ctx):
        return None
    red = scope_reduce.for_ctx(ctx)
    width = 2 * work_bd.sizes(ctx["config"])["blk"]
    kernel_s = sum(s for name, s in (red or {}).get("kernel_s", {}).items()
                   if re.sub(r"\.\d+$", "", name) == f"paged_attn_c{width}")
    if not kernel_s:
        return None
    work = narrow_work(scope_reduce.load_scoped(scope_reduce.newest_trace()),
                       width)
    if work is None:
        return None
    floor_s, _ = work_bd.attention_floor_s(
        ctx["config"], work_bd.device_peaks(), positions=work[0],
        pairs=work[1])
    return 100.0 * floor_s / kernel_s
