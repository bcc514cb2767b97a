"""The server child: the program's own entry point, `ratelimit_tpu.
runner.main()`, unchanged, in a process that (1) pins itself to the
server's cores before any thread exists and (2) on the way out writes
what JAX reports about the device's memory — only the process that
holds the chip can read it, and the program has no surface for it.

    python -m chipbench.served     (environment as for the runner, plus
                                    CHIPBENCH_CPUS, CHIPBENCH_DEVICE_REPORT)
"""

from __future__ import annotations

import json
import os
import sys


def main() -> int:
    cpus = os.environ.get("CHIPBENCH_CPUS")
    if cpus:
        os.sched_setaffinity(0, [int(c) for c in cpus.split(",")])
    from ratelimit_tpu import runner

    try:
        runner.main()
    finally:
        report = os.environ.get("CHIPBENCH_DEVICE_REPORT")
        if report and "jax" in sys.modules:
            import jax

            stats = [d.memory_stats() or {} for d in jax.local_devices()]
            with open(report, "w") as f:
                json.dump(
                    {"peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats]}, f
                )
    return 0


if __name__ == "__main__":
    sys.exit(main())
