"""Output tokens generated in the window, over the window's seconds."""


def read(run):
    return len(run.token_times_in_window()) / run.window_s
