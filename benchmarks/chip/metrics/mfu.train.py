"""Whole-step share of the chip's peak in the training window, in %: the
operations the 3D-GS math requires for the window's steps (work.py:
projection, compositing forward and backward, L1 + D-SSIM, Adam; no
assignment) over window seconds x chips x peak FLOP/s."""


def read(run):
    if not run.work or not run.window_s:
        return None
    return 100.0 * run.work["step_flops"] / (
        run.window_s * run.chips * run.peak["flops_per_s"])
