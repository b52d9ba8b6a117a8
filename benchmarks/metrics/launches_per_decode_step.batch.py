"""Programs launched in the traced window, eager operations included, over the DecodeStep launches among them: 1 is a turn that launches nothing but decode steps (device trace)."""
from phase_lib import launches_per_decode_step as read  # noqa: F401
