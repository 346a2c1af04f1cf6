"""compile.setup_s: seconds of JAX's trace, lower and backend-compile
events (compile-cache reads included) during set-up."""


def read(run):
    return run.compile_s["setup"]
