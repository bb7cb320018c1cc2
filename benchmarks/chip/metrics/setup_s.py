"""setup_s: seconds from process start to the window's start:
generating the network, deploying it, building the engine and warming
the cell's shape (compiling it on a checkout's first run)."""


def read(run):
    return run.setup_s
