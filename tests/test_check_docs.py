"""scripts/check_docs.py: the doc-reference checker must pass on the
repo's real docs and fail on deliberately broken references."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "scripts"))

import check_docs  # noqa: E402


def test_repo_docs_have_no_broken_references():
    errors = []
    for doc in check_docs.default_docs(ROOT):
        errors.extend(check_docs.check_file(doc, ROOT))
    assert errors == []


def test_missing_file_reference_fails():
    errs = check_docs.check_text(
        "see `serving/engine.py` and `serving/no_such_module.py`", ROOT)
    assert len(errs) == 1 and "no_such_module.py" in errs[0]


def test_missing_symbol_reference_fails():
    ok = check_docs.check_text(
        "`serving/engine.py::CascadeEngine` and "
        "`core/server.py::delta_for_escalation_rate`", ROOT)
    assert ok == []
    errs = check_docs.check_text(
        "`serving/engine.py::TotallyMadeUpSymbol`", ROOT)
    assert len(errs) == 1 and "TotallyMadeUpSymbol" in errs[0]


def test_dotted_symbol_components_are_all_checked():
    assert check_docs.check_text(
        "`serving/slots.py::TierSlotPool.ensure_blocks`", ROOT) == []
    errs = check_docs.check_text(
        "`serving/slots.py::TierSlotPool.frobnicate`", ROOT)
    assert len(errs) == 1 and "frobnicate" in errs[0]


def test_urls_and_globs_are_ignored():
    assert check_docs.check_text(
        "fetch https://example.com/missing/thing.py and scan `docs/*.md`",
        ROOT) == []


def test_absolute_output_paths_are_ignored():
    # output placeholders like `--trace-out /tmp/trace.json` are not
    # repo references; relative ones still fail
    assert check_docs.check_text(
        "run with `--trace-out /tmp/trace.json`", ROOT) == []
    assert check_docs.check_text(
        "run with `--trace-out trace.json`", ROOT)


def test_root_and_src_relative_paths_resolve():
    text = ("`README.md` `benchmarks/run.py` "
            "`repro/serving/engine.py` `kernels/prefill_attention.py`")
    assert check_docs.check_text(text, ROOT) == []


def test_main_exit_codes(tmp_path):
    good = tmp_path / "good.md"
    good.write_text("nothing to see\n")
    bad = tmp_path / "bad.md"
    bad.write_text("look at `definitely/not/a/file.py`\n")
    assert check_docs.main([str(good)]) == 0
    assert check_docs.main([str(good), str(bad)]) == 1


def test_find_refs_extracts_lineno_and_symbol():
    refs = check_docs.find_refs(
        "a\n`core/losses.py::ltc_loss` then `docs/serving.md`\n")
    assert refs == [(2, "core/losses.py", "ltc_loss"),
                    (2, "docs/serving.md", None)]
