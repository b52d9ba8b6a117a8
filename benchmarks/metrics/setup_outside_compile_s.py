"""`setup_s` less `setup_import_s` less the union of every compile record in set-up: what is left for the harness, the backend's start, the weights and the warm-up's execution (program counter)."""
import setup_lib
from phase_lib import import_seconds


def read(ctx):
    stages = setup_lib.union_s(ctx, setup_lib.DURATIONS)
    imported = import_seconds(ctx)
    if stages is None or imported is None:
        return None
    return ctx["setup_s"] - imported - stages
