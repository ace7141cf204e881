"""``benchmark/tools/lowered.py compare``: two lowered steps are the same
program when they differ in nothing but the source locations their kernel
bodies carry."""
import base64
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools import lowered  # noqa: E402


def _step(op: str, line: int) -> str:
    body = 'module { "%s"() : () -> () loc("kernel.py":%d:1) }' % (op, line)
    return ('stablehlo.custom_call @tpu_custom_call() {backend_config = '
            '"{\\22custom_call_config\\22: {\\22body\\22: \\22%s\\22}}"}'
            % base64.b64encode(body.encode()).decode())


@pytest.mark.parametrize("op,line,moved", [("k.add", 7, 0), ("k.mul", 3, 1)])
def test_compare_sees_past_locations_and_no_further(tmp_path, capsys, op,
                                                    line, moved):
    parent, change = tmp_path / "parent.json", tmp_path / "change.json"
    parent.write_text(json.dumps({"cell": _step("k.add", 3)}))
    change.write_text(json.dumps({"cell": _step(op, line)}))
    assert lowered.compare(str(parent), str(change)) == moved
    said = capsys.readouterr().out
    assert "kernel bodies 1 1" in said
    assert ("DIFFERENT" in said) == bool(moved)


def test_a_body_is_printed_without_its_locations():
    text = lowered.stripped(_step("k.add", 3))
    assert "BODY<" in text and "k.add" in text and "kernel.py" not in text
