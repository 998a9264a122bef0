"""Model step: useful operations of the predictions completed in the
traced window (each image's top-k experts, not capacity padding), per
second of the window, over the chip's peak."""


def read(ctx):
    if ctx.peak is None or not ctx.completed_in_window:
        return None
    win = ctx.window
    ops = sum(ctx.flops.prediction_flops(ctx.cfg, r.task_id)
              for r in win.due()
              if r.t_done is not None and r.t_done <= win.end)
    return 100.0 * ops / ctx.seconds / ctx.peak.flops_per_s
