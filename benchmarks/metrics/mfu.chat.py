"""Forward model operations of the tokens prefilled and generated in the window over window x peak."""
from metric_lib import mfu_serve as read  # noqa: F401
