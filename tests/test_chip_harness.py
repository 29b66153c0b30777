"""The chip benchmark's feed, refusals, bounded readings and roofline
arithmetic, collected in tier-1: the cases of
`benchmarks/chip/selftest/test_feed_cpu.py` (run by path there, in seconds,
with no `Trainer`), loaded from that file so that there is one copy of them.
The rest of `benchmarks/chip/selftest/` builds trainers for minutes and
stays run by path."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "chip", "selftest", "test_feed_cpu.py")
_spec = importlib.util.spec_from_file_location("chipbench_test_feed_cpu",
                                               _PATH)
_feed = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_feed)

# every test of the file, under its own name (parametrised cases and all)
globals().update({name: value for name, value in vars(_feed).items()
                  if name.startswith("test_")})
