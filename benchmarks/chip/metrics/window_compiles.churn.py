"""Programs traced inside the window and its drain (bucket rebuilds, new
shapes); 0 when set-up warmed everything the window runs."""


def read(run):
    return run.window_compiles
