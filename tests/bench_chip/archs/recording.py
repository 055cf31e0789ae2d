"""A test-only architecture: the dense GQA decoder's mathematics, taken
from ``benchmarks/chip/archs/dense_gqa.py`` loaded apart from the
harness's copy, with every call the benchmark makes into it recorded in
``CALLS`` by the function's name."""
import importlib.util
from pathlib import Path

_PATH = (Path(__file__).resolve().parents[3]
         / "benchmarks" / "chip" / "archs" / "dense_gqa.py")
_spec = importlib.util.spec_from_file_location("_recorded_dense_gqa", _PATH)
DENSE = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(DENSE)

CALLS = []


def _recorded(name):
    def call(*args, **kwargs):
        CALLS.append(name)
        return getattr(DENSE, name)(*args, **kwargs)
    call.__name__ = name
    return call


params = _recorded("params")
summarize = _recorded("summarize")
decode_step = _recorded("decode_step")
prefill_chunk = _recorded("prefill_chunk")
