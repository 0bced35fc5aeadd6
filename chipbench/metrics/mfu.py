"""Model step: images completed ok in the window times the model FLOPs per
image (2 x multiply-adds of the reference layer table), over the window
times the chips times the published bf16 peak, in %.  Replicated work on
a multi-chip submesh counts as waste."""


def read(run, name):
    """The metric's value in ``run``, or None where it has nothing to read."""
    flops = sum(r.images * run.flops_per_image[r.model]
                for r in run.ok_in_window())
    peak = run.peaks["bf16_flop_per_s"] * run.chips
    return 100.0 * flops / (run.window_s * peak)
