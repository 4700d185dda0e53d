"""Set-up seconds: from process start to the window's opening (loading,
building the deployment, the mix's set-up traffic, warming every shape
the cell uses from the compile cache or by compiling it)."""


def read(ctx):
    return ctx["setup_s"]
