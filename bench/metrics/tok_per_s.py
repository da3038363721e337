"""Prompt tokens prefilled plus output tokens generated inside the window,
over the window."""


def read(run):
    return run.tokens_per_s()
