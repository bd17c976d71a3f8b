"""End-to-end reader over NON-local filesystems.

Two schemes drive the fsspec fallback branch (fs.py:85-93) the way a real
object store would, without network:

* ``memory://`` - fsspec's in-process store: full write -> stamp -> read ->
  jax feed loop, plus multi-URL expansion.  Process pools cannot see another
  process's memory store, so these use thread/serial pools (the documented
  contract for non-re-derivable filesystems, fs.py:124-127).
* ``dir::file`` (fsspec DirFileSystem over a local dir, resolved from
  ``storage_options``) - re-derivable in a CHILD process, proving
  FilesystemFactory pickles into spawn workers and re-resolves there
  (reference: the serializable filesystem_factory, fs_utils.py:42-196).
"""

import numpy as np
import pytest

from petastorm_tpu.codecs import NdarrayCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.fs import get_filesystem_and_path
from petastorm_tpu.reader import make_batch_reader, make_reader
from petastorm_tpu.schema import Field, Schema

fsspec = pytest.importorskip("fsspec")

ROWS = 32


def _schema():
    return Schema("Remote", [
        Field("id", np.int64),
        Field("vec", np.float32, (4,), NdarrayCodec()),
    ])


def _rows(n=ROWS, base=0):
    return [{"id": base + i, "vec": np.full(4, base + i, np.float32)}
            for i in range(n)]


@pytest.fixture()
def memfs():
    fs = fsspec.filesystem("memory")
    yield fs
    # the memory store is a process-global singleton: isolate tests
    fs.store.clear()


def test_memory_scheme_write_stamp_read(memfs):
    url = "memory://ds_a"
    write_dataset(url, _schema(), _rows(), row_group_size_rows=8)
    # resolution went through the fsspec fallback, not pyarrow-native
    fs, path = get_filesystem_and_path(url)
    import pyarrow.fs as pafs

    assert isinstance(fs, pafs.PyFileSystem)
    with make_reader(url, reader_pool_type="thread", workers_count=2,
                     num_epochs=1, shuffle_row_groups=False) as r:
        rows = list(r)
    # thread pools deliver in completion order: compare as a set, check pairs
    assert sorted(row.id for row in rows) == list(range(ROWS))
    by_id = {int(row.id): row.vec for row in rows}
    np.testing.assert_array_equal(by_id[5], np.full(4, 5, np.float32))


def test_memory_scheme_jax_feed(memfs):
    import jax

    from petastorm_tpu.jax import JaxDataLoader

    url = "memory://ds_feed"
    write_dataset(url, _schema(), _rows(), row_group_size_rows=8)
    with make_batch_reader(url, reader_pool_type="thread", num_epochs=1,
                           shuffle_row_groups=False) as r:
        with JaxDataLoader(r, batch_size=8) as loader:
            batches = list(loader)
    assert len(batches) == 4
    got = np.concatenate([np.asarray(b["id"]) for b in batches])
    assert sorted(got.tolist()) == list(range(ROWS))
    assert isinstance(batches[0]["vec"], jax.Array)


def test_memory_scheme_multi_url_expansion(memfs):
    """A list of dataset file URLs over a remote scheme reads as one dataset
    (reference get_filesystem_and_path_or_paths, fs_utils.py:199-228)."""
    url_a, url_b = "memory://multi/ds_a", "memory://multi/ds_b"
    files_a = write_dataset(url_a, _schema(), _rows(16, base=0),
                            row_group_size_rows=8)
    files_b = write_dataset(url_b, _schema(), _rows(16, base=16),
                            row_group_size_rows=8)
    urls = [f"memory://{p}" for p in files_a + files_b]
    with make_reader(urls, reader_pool_type="serial", num_epochs=1,
                     shuffle_row_groups=False) as r:
        rows = list(r)
    assert sorted(row.id for row in rows) == list(range(32))


def test_memory_scheme_mixed_authority_rejected(memfs):
    from petastorm_tpu.errors import PetastormTpuError

    with pytest.raises(PetastormTpuError, match="share scheme"):
        make_reader(["memory://x/a.parquet", "other://x/b.parquet"])


def test_dir_scheme_process_pool_factory_pickling(tmp_path):
    """The fsspec-fallback filesystem re-resolves from (url, storage_options)
    inside a SPAWNED worker process - the full FilesystemFactory contract."""
    backing = tmp_path / "backing"
    backing.mkdir()
    url = "dir://ds"
    opts = {"path": str(backing), "target_protocol": "file"}
    write_dataset(url, _schema(), _rows(), row_group_size_rows=8,
                  storage_options=opts)
    assert (backing / "ds" / "_common_metadata").exists()  # really remote-backed
    with make_reader(url, reader_pool_type="process", workers_count=2,
                     num_epochs=1, shuffle_row_groups=False,
                     storage_options=opts) as r:
        rows = list(r)
    assert sorted(row.id for row in rows) == list(range(ROWS))
