#!/usr/bin/env python3
"""Record the small trace the yardstick's tests reduce (the builder's, on
the chip): a few launches of the program's kernel under the spans
``daemon_main.py`` writes, with idle gaps between them.

    python3 benchmark/tools/small_trace.py <out dir>
"""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    out = sys.argv[1]
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from seaweedfs_tpu.ec.codec import TpuCodec

    codec = TpuCodec()
    n = 1 << 20
    data = np.random.default_rng(0).integers(0, 256, (10, n), dtype=np.uint8)
    rows = codec.parity_rows
    np.asarray(codec.matmul_device(rows, codec.device_put(data)))  # compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    with TraceAnnotation("ec_encode_volume"):
        with TraceAnnotation("write_ec_files"):
            for _ in range(3):
                with TraceAnnotation("matmul_device", rows=4, k=10, n=n):
                    got = codec.matmul_device(rows, codec.device_put(data))
                np.asarray(got)
                time.sleep(0.02)
        time.sleep(0.05)  # the commit tail: nothing on the device
    jax.profiler.stop_trace()
    print("platform", jax.devices()[0].platform, jax.devices()[0].device_kind)


if __name__ == "__main__":
    main()
