"""Export-contract parity with the reference public API:
every name the reference exports from src/index.js:2-76 must
exist on ``yjs_tpu`` under the same (camelCase/JS) name.  The list is
parsed from the reference source itself so drift is impossible.

Documented deviations (asserted below so they stay deliberate):
- none — the full list resolves.  AbstractStruct is a stateless exported
  base that GC/Item genuinely subclass (core.py absorbs the reference's
  two concrete call paths into the subclasses; the base carries the
  contract).
"""

import re
from pathlib import Path

import pytest

import yjs_tpu as Y

_REF_INDEX = Path("/root/reference/src/index.js")


def _reference_exports() -> list[str]:
    src = _REF_INDEX.read_text()
    block = re.search(r"export\s*\{(.*?)\}", src, re.S).group(1)
    names = []
    for raw in block.split(","):
        raw = raw.split("//")[0].strip()  # strip trailing line comments
        if not raw:
            continue
        m = re.match(r"(\w+)(?:\s+as\s+(\w+))?$", raw)
        assert m, f"unparsed export entry: {raw!r}"
        names.append(m.group(2) or m.group(1))
    return names


@pytest.mark.skipif(not _REF_INDEX.exists(), reason="reference not present")
def test_reference_export_contract():
    names = _reference_exports()
    assert len(names) >= 70  # sanity: the whole list parsed
    missing = [n for n in names if not hasattr(Y, n)]
    assert not missing, f"exports missing vs reference index.js: {missing}"


def test_abstract_struct_is_the_real_base():
    assert issubclass(Y.Item, Y.AbstractStruct)
    assert issubclass(Y.GC, Y.AbstractStruct)
    # the base is stateless: subclass layouts are unchanged
    assert Y.AbstractStruct.__slots__ == ()


def test_js_type_aliases_are_identities():
    assert Y.Array is Y.YArray
    assert Y.Map is Y.YMap
    assert Y.Text is Y.YText
    assert Y.XmlText is Y.YXmlText
    assert Y.XmlElement is Y.YXmlElement
    assert Y.XmlFragment is Y.YXmlFragment
    assert Y.XmlHook is Y.YXmlHook


def test_create_delete_set_roundtrip():
    ds = Y.createDeleteSet()
    assert ds.clients == {}
    Y.add_to_delete_set(ds, 1, 0, 3)
    assert Y.is_deleted(ds, Y.createID(1, 2))
    assert not Y.is_deleted(ds, Y.createID(1, 3))
