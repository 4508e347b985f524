#!/usr/bin/env python3
"""Serve qwen2-72b at its published widths on one TPU chip through the
token-granular fleet path (the code behind ``launch/serve --fleet``).

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # 4-replica ("data",) fleet vs one device

The cuts (2 of 80 layers, bf16 weights) and the checks are in
``src/repro/launch/smoke.py``.  The last line of standard output is
``{"ok": true, "device": {...}}``; a failed check, or a host without a TPU,
exits non-zero before it.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

if __name__ == "__main__":
    from repro.launch.smoke import main

    sys.exit(main())
