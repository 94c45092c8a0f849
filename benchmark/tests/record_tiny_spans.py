"""Record the small trace with the program's spans in it that is kept
beside the tests (run once, by hand, on the chip): a few named programs
under ``GLOBAL_TRACER.span``s shaped like one wave of the served path,
with sleeps between, and a second thread blocked in ``scheduler.await``.

    python benchmark/tests/record_tiny_spans.py <out dir>

Five rounds. In each, the leader's thread dispatches ``jit_pilosa_topn``
and ``jit_pilosa_sum`` under ``executor.*`` spans, joins and reads back
under ``readback.join`` / ``readback.transfer``, sleeps 5 ms in
``pql.query``'s self time and 2 ms in ``pql.reply``, then 10 ms outside
any span; the follower's thread holds ``scheduler.await`` open from the
round's start to the end of the readback.
"""

import glob
import os
import shutil
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.executor.compile import named_jit
from pilosa_tpu.utils.tracing import GLOBAL_TRACER as T

ROUNDS = 5


def main() -> int:
    out = sys.argv[1]
    topn = named_jit("pilosa_topn", lambda m: jnp.bitwise_count(m).astype(jnp.int64).sum(axis=1))
    total = named_jit("pilosa_sum", lambda m: jnp.bitwise_count(m & (m >> 1)).astype(jnp.int64).sum())
    join = named_jit("pilosa_wave_join", lambda *flat: jnp.concatenate(flat))
    x = jnp.arange(1 << 22, dtype=jnp.uint32).reshape(32, -1)
    np.asarray(join(topn(x), total(x).reshape(1)))  # compiled before the trace

    # an event a round and side: none is cleared, so no wake-up can be lost
    started = [threading.Event() for _ in range(ROUNDS)]
    released = [threading.Event() for _ in range(ROUNDS)]

    def follower():
        for k in range(ROUNDS):
            started[k].wait()
            with T.span("http.query"), T.span("pql.query"), T.span("scheduler.await"):
                released[k].wait()

    tmp = os.path.join(out, "_trace")
    opts = jax.profiler.ProfileOptions()  # as benchmark/harness/serve.py
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    thread = threading.Thread(target=follower, daemon=True)
    thread.start()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for k in range(ROUNDS):
        started[k].set()
        with T.span("http.query"), T.span("pql.query", index="tiny"):
            with T.span("scheduler.await"), T.span("scheduler.wave", queries=2):
                with T.span("executor.TopN"):
                    a = topn(x)
                with T.span("executor.Sum"):
                    b = total(x).reshape(1)
                with T.span("scheduler.readback", arrays=2):
                    with T.span("readback.join"):
                        joined = join(a, b)
                    with T.span("readback.transfer"):
                        np.asarray(joined.block_until_ready())
                released[k].set()
            time.sleep(0.005)
            with T.span("pql.reply"):
                time.sleep(0.002)
        time.sleep(0.010)
    thread.join(timeout=10)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    shutil.copy(path, os.path.join(out, "tiny_spans.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out, "tiny_spans.xplane.pb")), jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
