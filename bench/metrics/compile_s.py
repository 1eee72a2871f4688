"""compile_s (s): seconds set-up spent in JAX's backend compile step
(`/jax/core/compile/backend_compile_duration`: a compile, or a load from
the persistent compilation cache), summed. Moves setup_s."""


def read(run):
    return run.compile_setup_s
