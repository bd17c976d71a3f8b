"""Images in the steps completed inside the window, over the window,
summed over the cell's chips."""


def read(rec):
    return rec["window"]["work"] / rec["window"]["seconds"]
