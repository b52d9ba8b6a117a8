"""Wall seconds of the calls that compiled one of the program's steps, `observability.ledger.compile_seconds()`; no step compiles inside the window, so all of it is set-up (program counter)."""
from phase_lib import compile_seconds as read  # noqa: F401
