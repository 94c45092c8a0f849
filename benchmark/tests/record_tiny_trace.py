"""Record the small device trace kept beside the tests (run once, by hand,
on the chip): a few jitted ops with idle gaps between them.

    python benchmark/tests/record_tiny_trace.py <out dir>
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main() -> int:
    out = sys.argv[1]
    f = jax.jit(lambda x: jnp.bitwise_count(x & (x >> 1)).sum())
    x = jnp.arange(1 << 22, dtype=jnp.uint32)
    f(x).block_until_ready()
    tmp = os.path.join(out, "_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(5):
        f(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    shutil.copy(path, os.path.join(out, "tiny.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out, "tiny.xplane.pb")), jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
