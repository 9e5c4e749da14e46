"""Tokens of the optimizer steps completed in the window / the window's
seconds (open to the end of its last step) / chips. Host clock around steps
that end in `block_until_ready`, in the worker."""


def read(run):
    if "step_end_s" not in run:
        return None
    return (run["steps"] * run["tokens_per_step"] / run["window_s"]
            / run["cell"]["chips"])
