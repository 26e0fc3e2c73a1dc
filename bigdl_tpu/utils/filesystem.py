"""URI-scheme filesystem dispatch (file://, hdfs://, s3://, gs://,
memory://) for checkpoints, model files and record datasets.

Parity: the reference treats remote storage as first-class — every
persistence path goes through hadoop-FS resolution
(DL/utils/File.scala `getFileSystem`: a path is a URI, the scheme picks
the filesystem, HDFS/S3 work wherever a local path does), and the
integration tier proves it (TEST/integration/HdfsSpec.scala,
TFRecord-on-HDFS via DL/utils/tf/TFRecordInputFormat.scala).

TPU-native design: the host-side IO plane uses `fsspec` (baked into the
image) the same way the reference uses hadoop-common — a scheme registry
the deployment can extend (install s3fs / gcsfs / the hdfs driver and
`s3://...` paths just work). Plain paths and `file://` URIs bypass fsspec
entirely so the hot local path costs nothing new. `memory://` is the
in-process fake the tests run against, standing in for a remote store.

Helpers mirror the subset of `os`/`open` the framework uses, each taking
a path-or-URI.

Resilience: every REMOTE operation (scheme-qualified paths other than
file://) runs under a `bigdl_tpu.resilience.RetryPolicy` — exponential
backoff + full jitter over transient failures, no retry of permanent
ones — because s3/gs/hdfs calls fail transiently as a matter of course
and a single blip must not kill a training run mid-checkpoint. Local
paths bypass the wrapper entirely (the hot path costs nothing new).
Swap the policy with `set_io_retry_policy` (tests use a no-sleep seeded
policy); each attempt passes the `fs.remote_io` fault-injection site, so
chaos tests can make any remote call flake deterministically.
"""

from __future__ import annotations

import os
import posixpath
from typing import List, Optional, Tuple

from bigdl_tpu.resilience import faults

_IO_RETRY = None  # lazily-built default RetryPolicy (see io_retry_policy)


def io_retry_policy():
    """The RetryPolicy guarding remote operations (3 retries, 0.2s base
    full-jitter backoff, 5s cap). Classified permanent beyond the
    defaults: ImportError (a missing fsspec backend driver — retrying
    cannot install it) and FileNotFoundError (a missing object is a
    definitive answer, and checkpoint scans probe for absent manifests
    as a matter of course — burning three backoff sleeps per miss would
    tax every resume scan)."""
    global _IO_RETRY
    if _IO_RETRY is None:
        from bigdl_tpu.resilience.retry import (DEFAULT_PERMANENT,
                                                RetryPolicy)
        _IO_RETRY = RetryPolicy(
            max_retries=3, base_delay_s=0.2, max_delay_s=5.0,
            permanent=DEFAULT_PERMANENT + (ImportError,
                                           FileNotFoundError),
            name="fs.remote_io")
    return _IO_RETRY


def set_io_retry_policy(policy) -> None:
    """Replace the remote-IO RetryPolicy (None restores the default)."""
    global _IO_RETRY
    _IO_RETRY = policy


def _remote(op: str, path, fn):
    """Run one remote call under the IO retry policy, passing the
    `fs.remote_io` fault site on every attempt."""
    def attempt():
        faults.fire("fs.remote_io", op=op, path=str(path))
        return fn()
    return io_retry_policy().call(attempt)


def is_uri(path: str) -> bool:
    """True for scheme-qualified paths (``scheme://...``)."""
    return "://" in str(path)


def _split(path: str) -> Tuple[Optional[str], str]:
    """(scheme or None, fs-local path)."""
    path = str(path)
    if not is_uri(path):
        return None, path
    scheme, rest = path.split("://", 1)
    scheme = scheme.lower()
    if scheme == "file":
        return None, "/" + rest.lstrip("/")
    return scheme, path


def _fs(scheme: str):
    """The fsspec filesystem for a scheme, with an actionable error when
    the backend driver isn't installed (s3 -> s3fs, gs -> gcsfs, ...)."""
    import fsspec
    try:
        return fsspec.filesystem(scheme)
    except ImportError as e:
        raise ImportError(
            f"URI scheme {scheme}:// needs its fsspec backend installed "
            f"({e}); local file paths and memory:// need nothing extra"
        ) from e


def join(base: str, *parts: str) -> str:
    """Path join that keeps URI schemes intact (posix separators for
    remote stores, os separators locally)."""
    scheme, _ = _split(base)
    if scheme is None:
        return os.path.join(base, *parts)
    return posixpath.join(str(base), *parts)


def open_file(path: str, mode: str = "rb"):
    scheme, local = _split(path)
    if scheme is None:
        return open(local, mode)
    import fsspec
    return _remote("open", path, lambda: fsspec.open(path, mode).open())


def exists(path: str) -> bool:
    scheme, local = _split(path)
    if scheme is None:
        return os.path.exists(local)
    return _remote("exists", path, lambda: _fs(scheme).exists(path))


def isdir(path: str) -> bool:
    scheme, local = _split(path)
    if scheme is None:
        return os.path.isdir(local)
    return _remote("isdir", path, lambda: _fs(scheme).isdir(path))


def makedirs(path: str, exist_ok: bool = True) -> None:
    scheme, local = _split(path)
    if scheme is None:
        os.makedirs(local, exist_ok=exist_ok)
    else:
        _remote("makedirs", path,
                lambda: _fs(scheme).makedirs(path, exist_ok=exist_ok))


def listdir(path: str) -> List[str]:
    """Child basenames (not full paths), matching os.listdir."""
    scheme, local = _split(path)
    if scheme is None:
        return os.listdir(local)
    return [posixpath.basename(p.rstrip("/"))
            for p in _remote("listdir", path,
                             lambda: _fs(scheme).ls(path, detail=False))]


def remove(path: str) -> None:
    scheme, local = _split(path)
    if scheme is None:
        os.remove(local)
    else:
        _remote("remove", path, lambda: _fs(scheme).rm(path))


def rename(src: str, dst: str) -> None:
    """Rename/move a file or directory tree. Locally this is os.rename —
    atomic within a filesystem, which is what makes the checkpoint
    commit-by-rename durable. Remote object stores have no rename at
    all, and fsspec's recursive mv (copy+delete) cannot be blind-retried:
    a second attempt over a half-moved tree hits FileNotFoundError on
    the already-deleted entries, and a mid-copy failure leaves a visible
    partial destination. So remote moves are decomposed into per-file
    copies (the target's parent created first) — each idempotent and
    individually retried, with manifest.json ordered LAST so a torn
    checkpoint publish has no manifest and stays invisible to resume
    scans — followed by a source delete that treats FileNotFoundError as
    already-done."""
    scheme, local_src = _split(src)
    _, local_dst = _split(dst)
    if scheme is None:
        os.rename(local_src, local_dst)
        return
    fs = _fs(scheme)
    sp_src = fs._strip_protocol(str(src)).rstrip("/")
    sp_dst = fs._strip_protocol(str(dst)).rstrip("/")
    if _remote("isdir", src, lambda: fs.isdir(sp_src)):
        names = _remote("find", src, lambda: fs.find(sp_src))
        made = set()
        for f in sorted(names, key=lambda p: (
                posixpath.basename(p) == "manifest.json", p)):
            rel = f[len(sp_src):].lstrip("/")
            target = posixpath.join(sp_dst, rel) if rel else sp_dst
            parent = posixpath.dirname(target)
            if parent not in made:
                # an object store has no directories; an HDFS-like store
                # refuses a copy into one that does not exist yet
                _remote("makedirs", parent, lambda d=parent: fs.makedirs(
                    d, exist_ok=True))
                made.add(parent)
            _remote("copy", f, lambda f=f, t=target: fs.copy(f, t))
    else:
        _remote("copy", src, lambda: fs.copy(sp_src, sp_dst))
    try:
        _remote("rm", src, lambda: fs.rm(sp_src, recursive=True))
    except FileNotFoundError:
        pass  # delete half already completed on a prior attempt


def rmtree(path: str) -> None:
    """Remove a directory tree (file trees on remote stores)."""
    scheme, local = _split(path)
    if scheme is None:
        import shutil
        shutil.rmtree(local)
    else:
        _remote("rmtree", path,
                lambda: _fs(scheme).rm(path, recursive=True))


def glob(pattern: str) -> List[str]:
    """Scheme-aware glob; remote results keep their scheme prefix.

    fsspec's fs.glob strips the protocol and, for authority-based
    schemes (hdfs://namenode:8020/...), the authority too — so the
    authority from the input pattern is restored on the way out.
    Bucket-based schemes (s3/gs) keep the bucket as the first path
    component and need only the scheme re-prefixed.
    """
    scheme, local = _split(pattern)
    if scheme is None:
        import glob as _glob
        return sorted(_glob.glob(local))
    fs = _fs(scheme)
    from urllib.parse import urlsplit
    parts = urlsplit(pattern)
    stripped = fs._strip_protocol(pattern)
    first_component = stripped.lstrip("/").split("/", 1)[0]
    authority_stripped = bool(parts.netloc) and first_component != parts.netloc
    if authority_stripped:
        prefix = f"{scheme}://{parts.netloc}/"
    elif not parts.netloc and parts.path.startswith("/"):
        # empty-authority form (hdfs:///user/...): keep the triple slash
        # so the first path segment is not promoted to a host
        prefix = f"{scheme}:///"
    else:
        prefix = f"{scheme}://"
    matches = _remote("glob", pattern, lambda: fs.glob(pattern))
    return sorted(prefix + p.lstrip("/") for p in matches)
