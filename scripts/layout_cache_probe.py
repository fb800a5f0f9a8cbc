#!/usr/bin/env python3
"""Does an executable loaded from the persistent compile cache keep a
layout pinned with jax.experimental.layout?  (PERF.md section 6, PR 27.)

    python3 scripts/layout_cache_probe.py

Runs the same small pool program in two processes, one after the other,
on a compile cache of their own: a fresh directory under ``TMPDIR`` that
this script makes, hands to both, and removes.  The first process
compiles (the pinned layout is kept), the second loads what the first
cached.

On JAX 0.9.0 (libtpu 0.0.34, and the CPU backend alike) the second process
gets its arrays back in the device's DEFAULT layout and the next pinned
program refuses them.  Run it again after a JAX upgrade before pinning any
layout on a program that goes through the cache.
"""
import subprocess
import sys
import tempfile
import time

PINNED = (0, 2, 3, 1, 4)


def child(tag: str, cache_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    fmt = Format(Layout(major_to_minor=PINNED),
                 SingleDeviceSharding(jax.devices()[0]))
    shape = (4, 32, 9, 64, 64)
    base = (jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
            % 251).astype(jnp.bfloat16)
    t0 = time.time()
    x = jax.device_put(base, fmt)
    print(tag, "device_put label:", x.format.layout, flush=True)
    if x.format.layout.major_to_minor != PINNED:
        # device_put's own transposing program came from the cache too.
        jax.config.update("jax_enable_compilation_cache", False)
        x = jax.device_put(base, fmt)
        jax.config.update("jax_enable_compilation_cache", True)
        print(tag, "device_put label, cache off:", x.format.layout,
              flush=True)

    def upd(p, i, rows):
        def body(carry, j):
            return carry.at[j, :, 1 + i, 2].set(rows), None
        p, _ = jax.lax.scan(body, p, jnp.arange(4))
        return p.sum(axis=(0, 1, 3, 4)).astype(jnp.float32), p

    f = jax.jit(upd, in_shardings=(fmt, None, None),
                out_shardings=(None, fmt), donate_argnums=(0,))
    rows = jnp.full((32, 64), -3.0, jnp.bfloat16)
    want = base
    for step in range(3):
        try:
            comp = f.lower(x, step, rows).compile()
            _, x = f(x, step, rows)
        except Exception as e:
            print(tag, f"call {step} raised:", str(e).splitlines()[0][:200],
                  flush=True)
            break
        want = want.at[:, :, 1 + step, 2].set(-3.0)
        ok = bool((np.asarray(x.astype(jnp.float32))
                   == np.asarray(want.astype(jnp.float32))).all())
        print(tag, f"call {step}: out label {x.format.layout}; compiled "
              f"says {comp.output_formats[1].layout}; values ok {ok}",
              flush=True)
    print(tag, "took", round(time.time() - t0, 1), "s", flush=True)


def main() -> int:
    if len(sys.argv) == 3:
        child(sys.argv[1], sys.argv[2])
        return 0
    # This process never touches jax: each child has the chip to itself.
    with tempfile.TemporaryDirectory(prefix="layout_cache_probe.") as cache:
        for tag in ("cold", "warm"):
            rc = subprocess.run([sys.executable, __file__, tag, cache]
                                ).returncode
            if rc:
                return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
