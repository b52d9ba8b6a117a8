"""Config-driven single-op benchmark harness.

The analog of the reference's op benchmark tester
(paddle/fluid/operators/benchmark/op_tester.h:30 + op_tester_config.h) —
time ONE op at given shapes/dtypes to localize regressions, instead of
inferring from end-to-end steps.

Timing method:
  - the op runs R times inside ONE jitted ``lax.scan`` so a single device
    dispatch amortizes the host's launch cost;
  - the scan carry perturbs the op's first input each iteration, which
    defeats XLA loop-invariant code motion (a loop whose body does not
    depend on the carry is hoisted and executes ONCE — every naive
    timing loop here measures dispatch latency, not the op);
  - the warmup call uses different operand values than the timed call;
  - the barrier is a device_get of a small output slice.

Usage::

    from paddle_tpu.utils.op_bench import bench_op, run_suite
    ms = bench_op(lambda x, w: x @ w, [(1024, 1024), (1024, 1024)])
    rows = run_suite()           # the built-in conv/bn/matmul suite
    python -m paddle_tpu.utils.op_bench [config.json]

Config file: a JSON list of rows ``{"name": ..., "op": "<expr over jnp,
jax, args a,b,c>", "shapes": [[...], ...], "dtype": "bfloat16",
"repeat": 50}``.
"""
from __future__ import annotations

import json
import sys
import time
from typing import Callable, Sequence

import numpy as np

__all__ = ["bench_op", "run_suite", "DEFAULT_SUITE", "scan_overhead_ms"]

_overhead_ms = None


def scan_overhead_ms() -> float:
    """Per-iteration overhead of the chained-scan timing loop itself,
    measured once per process on a trivially small op. Subtracted from
    every measurement (``ms_net``): it would otherwise swamp
    sub-millisecond ops."""
    global _overhead_ms
    if _overhead_ms is None:
        import jax
        import jax.numpy as jnp

        a = jax.device_put(jnp.zeros((8, 128), jnp.float32))

        @jax.jit
        def run(a):
            def body(c, _):
                return (a + c).ravel()[0] * 1e-30, None

            c, _ = jax.lax.scan(
                body, jnp.zeros((), jnp.float32), None, length=200
            )
            return c

        _ = np.asarray(run(a))
        best = float("inf")
        for i in range(3):  # keep the best of 3
            t0 = time.perf_counter()
            _ = np.asarray(run(a + (i + 1)))
            best = min(best, (time.perf_counter() - t0) / 200 * 1e3)
        _overhead_ms = best
    return _overhead_ms


def bench_op(
    op: Callable,
    shapes: Sequence[Sequence[int]],
    dtype="float32",
    repeat: int = 50,
    flops: float | None = None,
) -> dict:
    """Time one op. Returns {ms, gbps_read, tflops (if flops given)}.

    ``op`` takes jnp arrays (one per entry of ``shapes``) and returns an
    array or tuple of arrays.
    """
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(0)
    args = [
        jax.device_put(jnp.asarray(
            rng.rand(*s).astype(np.float32) - 0.5).astype(dt))
        for s in shapes
    ]

    @jax.jit
    def run(*args):
        def body(carry, _):
            # perturb the first operand with the carry: forces the body to
            # stay inside the loop (no LICM) and re-read every operand
            a0 = args[0] + carry.astype(args[0].dtype)
            out = op(a0, *args[1:])
            leaf = out[0] if isinstance(out, (tuple, list)) else out
            return jnp.ravel(leaf)[0].astype(jnp.float32) * 1e-30, None

        carry, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.float32), None, length=repeat
        )
        return carry

    warm_args = [a + 1 for a in args]
    _ = np.asarray(run(*warm_args))  # compile + warm on DIFFERENT values
    dt_s = float("inf")
    for _i in range(3):  # keep the best of 3
        t0 = time.perf_counter()
        _ = np.asarray(run(*args))
        dt_s = min(dt_s, (time.perf_counter() - t0) / repeat)

    in_bytes = sum(
        int(np.prod(s)) * jnp.dtype(dtype).itemsize for s in shapes
    )
    ovh_s = scan_overhead_ms() / 1e3
    net_s = max(dt_s - ovh_s, 0.0)
    row = {
        "ms": round(dt_s * 1e3, 4),
        "ms_net": round(net_s * 1e3, 4),
        "overhead_ms": round(ovh_s * 1e3, 4),
    }
    if net_s < 0.5 * dt_s:
        # the scan-loop overhead dominates: the op is faster than the
        # harness can resolve on this platform — treat rates as lower
        # bounds only
        row["overhead_bound"] = True
    rate_s = max(net_s, 0.25 * dt_s)
    row["gbps_read"] = round(in_bytes / rate_s / 1e9, 1)
    if flops is not None:
        row["tflops"] = round(flops / rate_s / 1e12, 2)
    return row


def _conv2d(stride=1, pad=0):
    import jax

    def op(x, w):
        dn = jax.lax.conv_dimension_numbers(
            x.shape, w.shape, ("NHWC", "HWIO", "NHWC")
        )
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=dn,
        )

    return op


def _bn_stats(x):
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    return jnp.mean(xf, axis=(0, 1, 2)), jnp.mean(
        jnp.square(xf), axis=(0, 1, 2)
    )


def _suite():
    import jax.numpy as jnp

    def conv_flops(n, h, w, cin, cout, k, stride):
        oh, ow = h // stride, w // stride
        return 2.0 * n * oh * ow * cin * cout * k * k

    return [
        # MXU calibration
        ("matmul_4096_bf16", lambda a, b: a @ b,
         [(4096, 4096), (4096, 4096)], "bfloat16", 2.0 * 4096 ** 3),
        # ResNet-50 conv shapes (NHWC)
        ("conv_stem_7x7s2", _conv2d(2, 3),
         [(256, 224, 224, 3), (7, 7, 3, 64)], "bfloat16",
         conv_flops(256, 224, 224, 3, 64, 7, 2)),
        ("conv_1x1_c64_256", _conv2d(1, 0),
         [(256, 56, 56, 64), (1, 1, 64, 256)], "bfloat16",
         conv_flops(256, 56, 56, 64, 256, 1, 1)),
        ("conv_3x3_c128", _conv2d(1, 1),
         [(256, 28, 28, 128), (3, 3, 128, 128)], "bfloat16",
         conv_flops(256, 28, 28, 128, 128, 3, 1)),
        ("conv_3x3_c512", _conv2d(1, 1),
         [(256, 7, 7, 512), (3, 3, 512, 512)], "bfloat16",
         conv_flops(256, 7, 7, 512, 512, 3, 1)),
        # VPU / HBM: per-channel stat reductions (the BN hot spot)
        ("bn_stats_c64", _bn_stats, [(256, 56, 56, 64)], "bfloat16", None),
        ("bn_stats_c256", _bn_stats, [(256, 56, 56, 256)], "bfloat16", None),
        ("bn_stats_c1024", _bn_stats, [(256, 14, 14, 1024)], "bfloat16",
         None),
        # elementwise HBM
        ("ew_add_411MB", lambda a, b: a + b,
         [(256, 56, 56, 256), (256, 56, 56, 256)], "bfloat16", None),
        ("softmax_s2048", lambda a: jnp.exp(
            a - a.max(-1, keepdims=True)), [(32, 2048, 2048)], "bfloat16",
         None),
    ]


DEFAULT_SUITE = [row[0] for row in _suite()]


def run_suite(names=None) -> list[dict]:
    rows = []
    for name, op, shapes, dtype, flops in _suite():
        if names and name not in names:
            continue
        r = bench_op(op, shapes, dtype=dtype, flops=flops)
        r["name"] = name
        rows.append(r)
    return rows


def _run_config(path: str) -> list[dict]:
    import jax  # noqa: F401  (exposed to config expressions)
    import jax.numpy as jnp  # noqa: F401

    with open(path) as f:
        cfg = json.load(f)
    rows = []
    for item in cfg:
        ns = {"jnp": jnp, "jax": jax, "np": np}
        arity = len(item["shapes"])
        argnames = ["a", "b", "c", "d"][:arity]
        fn = eval(  # noqa: S307 — explicit user-provided config expression
            f"lambda {', '.join(argnames)}: {item['op']}", ns
        )
        r = bench_op(
            fn,
            item["shapes"],
            dtype=item.get("dtype", "float32"),
            repeat=item.get("repeat", 50),
            flops=item.get("flops"),
        )
        r["name"] = item.get("name", item["op"])
        rows.append(r)
    return rows


if __name__ == "__main__":
    out = (
        _run_config(sys.argv[1]) if len(sys.argv) > 1 else run_suite()
    )
    for r in out:
        print(json.dumps(r))
