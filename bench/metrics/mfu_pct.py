"""Model FLOPs of the window's tokens over the window's whole length,
as a share of the chip's peak.  A token counts 2 per matmul parameter
(the embedding is a lookup, not a matmul; the head only where the step
computes it) plus attention over its causal context, from the model's
shapes.  Source: the host clock at the window's opening and close."""


def read(r):
    s, flops, wall = r.shapes, 0.0, r.rec.seconds
    for st in r.rec.window():
        for n, start, head in st.segments:
            # contexts start+1 .. start+n, and flops are linear in context
            flops += n * s.token_flops(start + (n + 1) / 2.0, head)
    if flops <= 0 or wall <= 0:
        return None
    return 100.0 * flops / wall / r.peaks["bf16_flops_per_s"] / r.cell.chips
