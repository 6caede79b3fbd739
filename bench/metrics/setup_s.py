"""Seconds from process start to the start of the measured window:
imports, accelerator start-up, weights, deploy(), traffic, warm-up
(host clock)."""


def read(ctx):
    return ctx["setup_s"]
