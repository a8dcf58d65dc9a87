"""Docs-sync gate: the documentation cannot silently rot again.

Three contracts (ISSUE: nine PRs of growth outran the docs once):

* every public symbol exported by ``repro.exec`` is mentioned in
  DESIGN.md or ARCHITECTURE.md;
* every configuration and cell that ``BENCHMARK.json`` declares is named
  in PERF.md's §4 (Cells);
* every intra-repo markdown link resolves — file and, for ``#anchor``
  links, the GitHub-style heading slug.
"""
from __future__ import annotations

import json
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = os.path.join(REPO, "docs")


def _read(path: str) -> str:
    with open(path, encoding="utf-8") as f:
        return f.read()


def test_every_exec_export_is_documented():
    import repro.exec as exec_pkg

    corpus = _read(os.path.join(DOCS, "DESIGN.md")) + _read(
        os.path.join(DOCS, "ARCHITECTURE.md"))
    missing = [s for s in exec_pkg.__all__ if s not in corpus]
    assert not missing, (
        f"public repro.exec exports undocumented in DESIGN.md/"
        f"ARCHITECTURE.md: {missing}")


def test_every_bench_section_has_a_schema_entry():
    bench = json.loads(_read(os.path.join(REPO, "BENCHMARK.json")))
    perf = _read(os.path.join(REPO, "PERF.md"))
    cells = re.search(r"^## 4\..*?(?=^## 5\.)", perf, re.M | re.S)
    assert cells, "PERF.md has no '## 4.' (Cells) section before '## 5.'"
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]])
    missing = [n for n in names if f"`{n}`" not in cells.group(0)]
    assert not missing, (
        f"BENCHMARK.json configurations and cells not named in PERF.md "
        f"§4: {missing}")


def _github_slug(heading: str) -> str:
    text = heading.strip().lstrip("#").strip().replace("`", "")
    text = re.sub(r"[^\w\- ]", "", text, flags=re.UNICODE).lower()
    return text.replace(" ", "-")


def _markdown_files():
    for base in (REPO, DOCS):
        for name in os.listdir(base):
            if name.endswith(".md"):
                yield os.path.join(base, name)


def test_intra_repo_markdown_links_resolve():
    link_re = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
    broken = []
    for md in _markdown_files():
        text = _read(md)
        # markdown links only; skip external and pure-anchor targets
        for target in link_re.findall(text):
            if target.startswith(("http://", "https://", "mailto:")):
                continue
            path, _, anchor = target.partition("#")
            dest = md if not path else os.path.normpath(
                os.path.join(os.path.dirname(md), path))
            if not os.path.exists(dest):
                broken.append(f"{os.path.relpath(md, REPO)}: {target} "
                              f"(missing file)")
                continue
            if anchor and dest.endswith(".md"):
                slugs = {_github_slug(line)
                         for line in _read(dest).splitlines()
                         if line.startswith("#")}
                if anchor not in slugs:
                    broken.append(f"{os.path.relpath(md, REPO)}: {target} "
                                  f"(missing anchor)")
    assert not broken, "broken intra-repo markdown links:\n" + "\n".join(
        broken)
