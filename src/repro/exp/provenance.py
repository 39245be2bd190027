"""Provenance records for benchmark artifacts.

Every BENCH_*.json this repo writes carries a `provenance` block — the
git revision, JAX version, backend platform, device kind and count,
and the SHA-256 of the serialized `ExperimentSpec` that produced the
numbers — so a benchmark file is attributable to an exact code + spec +
device triple without relying on the commit that happened to check it
in.
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess


def git_revision(repo_dir: str | None = None) -> tuple:
    """`(rev, dirty)`: the current git commit and whether the tree has
    local edits.  The dirty flag is a separate boolean — not a '-dirty'
    suffix — so `git_rev` is always a parseable 40-hex revision tools
    can feed straight back to git.  `('unknown', False)` outside a git
    checkout."""
    if repo_dir is None:
        repo_dir = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=repo_dir, check=True,
            capture_output=True, text=True, timeout=10).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=repo_dir, check=True,
            capture_output=True, text=True, timeout=10).stdout.strip()
        return rev, bool(dirty)
    except Exception:
        return "unknown", False


def spec_hash(spec) -> str:
    """SHA-256 of the canonical (sorted-key) JSON form of an
    `ExperimentSpec` — stable across processes and field ordering."""
    payload = json.dumps(spec.to_dict(), sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()


def provenance(spec=None) -> dict:
    """The provenance block benchmarks embed in their BENCH_*.json."""
    import jax
    rev, dirty = git_revision()
    devs = jax.devices()
    out = dict(
        git_rev=rev,
        dirty=dirty,
        jax_version=jax.__version__,
        backend=jax.default_backend(),
        platform=devs[0].platform,
        device_kind=devs[0].device_kind,
        device_count=len(devs),
    )
    if spec is not None:
        out["spec_sha256"] = spec_hash(spec)
    return out
