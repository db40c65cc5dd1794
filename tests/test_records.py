"""The documents describe the tree that is there.

A speed is written in ``PERF.md`` and nowhere else; a file a document names
exists; a cell the benchmark runs is described where a user looks; and what
PR 48 deleted is named by nothing that stayed.  These fail when code is
deleted and a document is not told.
"""

import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(path):
    with open(os.path.join(ROOT, path), encoding="utf-8") as f:
        return f.read()


SKIPPED_DIRS = {
    ".git", "__pycache__", ".pytest_cache", ".hypothesis", ".jax_cache",
    "chiprun_out", ".archive_check", "out",
}
DOCUMENTS = ["README.md", "PERF.md", "PARITY.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
)
# The reference implementation's own files, a published model's file, and the
# name a user gives a run's log: named on purpose, never in this tree.
NOT_OURS = {
    "dpwa/conn.py", "dpwa/config.py", "dpwa/interpolation.py",
    "adapters/pytorch.py", "config.json", "metrics.jsonl", "m.jsonl",
}
NAMED_FILE = re.compile(r"[\w./*<>{},\[\]-]*\.(?:jsonl|json|py|md|cpp|yaml)\b")
RATE = re.compile(
    r"[0-9][0-9.,]* ?(GB/s|Gb/s|Gbps|MB/s|steps/s|samples/s|tokens/s|tok/s"
    r"|img/s|% ?MFU|ms/step|ms per step|frames/s|fps)"
)
WORKLOADS = [w["name"] for w in json.loads(read("BENCHMARK.json"))["workloads"]]


def tree_files():
    found = []
    for here, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in SKIPPED_DIRS]
        found += [os.path.relpath(os.path.join(here, f), ROOT) for f in files]
    return found


def perf_sections(text):
    """``PERF.md`` by the number in its ``## N.`` headings."""
    parts = re.split(r"^## (\d+)\.", text, flags=re.M)
    return {int(n): body for n, body in zip(parts[1::2], parts[2::2])}


def without_history(path):
    """A document's text as it describes the present: of ``PERF.md``
    sections 1-5 and 7 (section 6 is history, and names what PRs deleted)."""
    text = read(path)
    if path != "PERF.md":
        return text
    return "\n".join(b for n, b in sorted(perf_sections(text).items()) if n != 6)


def missing_files(text, files):
    names = {os.path.basename(f) for f in files}
    missing = []
    for quoted in re.findall(r"`([^`\n]+)`", text):
        for m in NAMED_FILE.finditer(quoted):
            name = m.group(0)
            outside = name.startswith("/")  # the driver's files, not the repo's
            if outside or name in NOT_OURS or re.search(r"[<>{}\[\]]", name):
                continue
            name = name.lstrip("./")
            if "*" in name:
                pattern = re.compile(
                    "(^|/)" + re.escape(name).replace(r"\*", "[^/]*") + "$"
                )
                held = any(pattern.search(f) for f in files)
            elif "/" in name:
                held = any(f == name or f.endswith("/" + name) for f in files)
            else:
                held = name in names
            if not held:
                missing.append(name)
    return sorted(set(missing))


@pytest.fixture(scope="module")
def files():
    return tree_files()


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_a_document_names_only_what_the_tree_has(doc, files):
    text = without_history(doc)
    assert missing_files(text, files) == []
    if doc != "PERF.md":
        rates = [m.group(0) for m in RATE.finditer(text)]
        assert rates == [], f"{doc} states a rate; PERF.md is where a speed is written"


@pytest.mark.parametrize("cell", WORKLOADS)
def test_a_cell_is_described_where_a_user_looks(cell):
    assert f"`{cell}`" in perf_sections(read("PERF.md"))[4]
    assert f"`{cell}`" in read("README.md")


def test_nothing_names_what_this_pr_deleted(files):
    # Written in halves, so that this file does not name them either.
    scripts = [
        "attention_memory", "flash_ring_bench", "llama_block_bench",
        "mfu_accounting", "mfu_roofline_all", "mixing_128", "pair_merge_sweep",
        "pool_convergence", "resnet20_roofline", "resnet20_trace",
        "spec_scale_bert", "spec_scale_resnet20", "spec_scale_train",
        "stacked_exchange_profile", "train_steps_refresh",
        "wire_compression_bench",
    ]
    gone = [s + ".py" for s in scripts] + [
        "bench" + ".py", "bench" + "_history", "pallas_pair" + "_merge",
        "pallas_pairwise" + "_merge", "BASELINE" + ".md", "RESULTS" + ".md",
        "docs/artifacts" + ".md",
    ]
    # History, the issue itself, and the reference's papers, which are not
    # the builders' to edit.
    exempt = {
        "CHANGES.md", "ROADMAP.md", "ISSUE.md", "SURVEY.md", "PAPER.md",
        "PAPERS.md", "SNIPPETS.md",
    }
    named = []
    for path in files:
        if path in exempt or not path.endswith((".py", ".md", ".toml", ".json")):
            continue
        text = without_history(path)
        named += [(path, g) for g in gone if g in text]
    assert named == []
