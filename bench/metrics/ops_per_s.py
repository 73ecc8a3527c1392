"""Operations completed in the window, over the window's time."""


def read(run):
    if run.window_s <= 0 or run.ops == 0:
        return None
    return run.ops / run.window_s
