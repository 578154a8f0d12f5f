"""Set-up seconds: process start to the window's opening (imports and the
TPU runtime, the deployment, the simulator and its layout, compile or
cache load, the cold solve)."""


def read(run):
    return run.setup_s
