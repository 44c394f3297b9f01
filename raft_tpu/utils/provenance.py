"""Which code produced an artifact: the git commit of the checkout."""

from __future__ import annotations

import os
import subprocess
from typing import Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def git_commit(repo: Optional[str] = None) -> str:
    """Short HEAD, with ``-dirty`` when the tree has uncommitted
    changes; ``"unknown"`` outside a git checkout (the chip tool's copy
    is not one) or where git is missing — never an error."""
    repo = repo or REPO_ROOT
    try:
        r = subprocess.run(["git", "-C", repo, "rev-parse", "--short",
                            "HEAD"], capture_output=True, text=True,
                           timeout=10)
        if r.returncode != 0 or not r.stdout.strip():
            return "unknown"
        s = subprocess.run(["git", "-C", repo, "status", "--porcelain"],
                           capture_output=True, text=True, timeout=10)
        return r.stdout.strip() + ("-dirty" if s.stdout.strip() else "")
    except (OSError, subprocess.SubprocessError):
        return "unknown"
