"""Index predict (``serve/index``): XLA compiles (persistent-cache reads
included) inside the window; set-up warms every batch size, so this
should read 0."""


def read(run):
    if "serve_stats" not in run.layer:
        return None
    return run.window_compiles
