"""README example: a configuration that differs from an existing one in its
sizes only re-exports that one's builder. Found by its name; edits nothing."""

from pathlib import Path

from harness.cells import load_module

_base = load_module(Path(__file__).with_name("spectrum_fir64_fft2048.py"))
make_kernel = _base.make_kernel
make_input = _base.make_input
reference = _base.reference
judge = _base.judge
frame_cost = _base.frame_cost
