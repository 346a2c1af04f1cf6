"""compile.window_s: seconds of JAX's trace, lower and backend-compile
events inside the window; 0 when nothing compiles there."""


def read(run):
    return run.compile_s["window"]
