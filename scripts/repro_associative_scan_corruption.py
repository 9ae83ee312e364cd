"""Minimal repro: jax.lax.associative_scan(min, reverse=True) was seen to
silently produce corrupt suffix minima on a TPU at ~2800-length axes — the
reason babble_tpu.tpu.kernels.suffix_min exists as an explicit log-step
shift-doubling instead. Whether the installed stack still does is what
this script reports; CHANGES.md (PR 21) records the last chip reading.

Run on a TPU host:
    python scripts/repro_associative_scan_corruption.py
Healthy output says "associative_scan MATCHES numpy" on every case;
the corruption manifests as a nonzero mismatch count at the larger shapes
(no exception — that is what makes it dangerous).

Pinned by tests/test_frontier.py::test_suffix_min_matches_numpy, which
asserts the replacement (suffix_min) against a numpy oracle at the same
shapes, so the workaround cannot be "simplified" back to associative_scan
without the suite noticing.
"""

import functools

import numpy as np

# (what the scan combines, reverse?, scanned axis, shape): the reverse-min
# form kernels.suffix_min replaced, at the lengths where it was seen to
# corrupt, and the forward-max form doubling._closure_la still uses along
# the chain axis of its (N, L, N) table
CASES = [
    ("min", True, 2, (4, 5, 128)),
    ("min", True, 2, (4, 5, 1024)),
    ("min", True, 2, (4, 5, 2048)),
    ("min", True, 2, (4, 5, 2801)),
    ("min", True, 2, (4, 5, 4096)),
    ("max", False, 1, (64, 1024, 64)),
    ("max", False, 1, (8, 2801, 8)),
    ("max", False, 1, (8, 4096, 8)),
]


def check():
    """{case label: cells where lax.associative_scan differs from numpy}
    on JAX's default platform; all zeros on a healthy stack.
    chip_smoke.py runs this on the chip in its cold64 phase."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    out = {}
    for op, reverse, axis, shape in CASES:
        x = rng.integers(0, 3000, size=shape).astype(np.int32)
        fn, acc = (
            (jnp.minimum, np.minimum) if op == "min"
            else (jnp.maximum, np.maximum)
        )
        # jitted, as both kernels would run it (eagerly the scan is
        # hundreds of one-op programs)
        scan = jax.jit(functools.partial(
            jax.lax.associative_scan, fn, reverse=reverse, axis=axis,
        ))
        got = np.asarray(scan(jnp.asarray(x)))
        xs = np.flip(x, axis) if reverse else x
        want = acc.accumulate(xs, axis=axis)
        if reverse:
            want = np.flip(want, axis)
        label = f"{op}{'_rev' if reverse else ''}_axis{axis}_{'x'.join(map(str, shape))}"
        out[label] = int((got != want).sum())
    return out


def main():
    import jax

    print("platform:", jax.devices()[0].platform)
    for label, bad in check().items():
        verdict = "MATCHES numpy" if bad == 0 else f"CORRUPT ({bad} cells)"
        print(f"{label}: associative_scan {verdict}")


if __name__ == "__main__":
    main()
