"""Loss-masked (non-pad) tokens in the steps completed inside the window,
over the window."""


def read(rec):
    return rec["window"]["work"] / rec["window"]["seconds"]
