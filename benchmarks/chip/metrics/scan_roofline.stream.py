"""The device scan's share of its roofline, in percent, in the streamed
assessment of an N-Triples dump: read as ``scan_roofline`` reads it, with
one pass a chunk."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "scan_roofline", os.path.join(os.path.dirname(__file__),
                                  "scan_roofline.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
read = _mod.read
