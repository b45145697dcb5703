"""What the five set-up metrics share: the program's own set-up ledger
(`paddle_tpu.obs.goodput.compile_ledger()`: every program's trace, lower
and compile-or-load seconds by name from JAX's own compile events, and the
program's start-up phases), as it stood when the harness entered the window
(`Window.__enter__` calls the sentinel's `mark_warm()`, which freezes it to
`at_warm`). `setup_s` times set-up from outside; these say which part of it
the program spent tracing, lowering, compiling or loading, by its own
count. What is left of `setup_s` is the benchmark's own: the device's first
touch, the seeded weights, the check's steps and reference, the ramp."""
LAYER = "Set-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def at_warm():
    """The frozen ledger, or None on a program without one (or one that
    never turned warm)."""
    try:
        from paddle_tpu.obs.goodput import compile_ledger
    except ImportError:
        return None
    return compile_ledger().at_warm
