"""Generate docs/api/*.md from the live package (autodoc-style).

Run from the repo root::

    python docs/gen_api_reference.py

One markdown file per public module: each documented symbol gets its
signature and full docstring.  Regenerate after changing any public
docstring/signature; tests assert the committed output is current
(tests/test_api_docs.py).
"""

from __future__ import annotations

import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: module -> ordered public symbols (None = use module __all__ or everything
#: public it defines).  This is the DOCUMENTED API surface; additions belong
#: here the moment they are public.
API = [
    ("petastorm_tpu.reader", ["make_reader", "make_batch_reader",
                              "elastic_resume", "Reader"]),
    ("petastorm_tpu.schema", ["Schema", "Field"]),
    ("petastorm_tpu.codecs", ["Codec", "ScalarCodec", "NdarrayCodec",
                              "CompressedNdarrayCodec", "CompressedImageCodec",
                              "register_codec"]),
    ("petastorm_tpu.transform", ["TransformSpec", "transform_schema",
                                 "transform_signature",
                                 "transform_output_cacheable",
                                 "transform_cache_info"]),
    ("petastorm_tpu.predicates", ["in_set", "in_intersection", "in_lambda",
                                  "in_negate", "in_reduce",
                                  "in_pseudorandom_split"]),
    ("petastorm_tpu.selectors", ["SingleIndexSelector", "IntersectIndexSelector",
                                 "UnionIndexSelector"]),
    ("petastorm_tpu.ngram", ["NGram"]),
    ("petastorm_tpu.weighted_sampling", ["WeightedSamplingReader"]),
    ("petastorm_tpu.sequence.dataset", ["token_field", "is_sequence_field",
                                        "make_sequence_reader",
                                        "iter_documents"]),
    ("petastorm_tpu.sequence.packing", ["SequencePacker", "iter_packed_rows",
                                        "iter_packed_blocks",
                                        "iter_ragged_batches",
                                        "packed_stream_digest"]),
    ("petastorm_tpu.sequence.mixing", ["make_mixed_sequence_reader",
                                       "corpus_seed"]),
    ("petastorm_tpu.sequence.loader", ["PackedSequenceReader",
                                       "make_packed_sequence_loader"]),
    ("petastorm_tpu.seeding", ["seed_stream", "derive_seed", "StreamDigest",
                               "reader_buffer_seed",
                               "resolve_deterministic"]),
    ("petastorm_tpu.shuffle", ["RandomShufflingBuffer", "NoopShufflingBuffer"]),
    ("petastorm_tpu.jax.loader", ["JaxDataLoader", "make_jax_loader"]),
    ("petastorm_tpu.jax.checkpoint", ["make_checkpoint_manager",
                                      "save_checkpoint", "restore_checkpoint",
                                      "resume_reader_kwargs"]),
    ("petastorm_tpu.jax.device_buffer", ["DeviceShufflingBuffer"]),
    ("petastorm_tpu.pytorch", ["DataLoader", "BatchedDataLoader"]),
    ("petastorm_tpu.tf", ["make_petastorm_dataset", "tf_tensors"]),
    ("petastorm_tpu.spark", ["dataset_as_rdd", "as_spark_schema",
                             "dict_to_spark_row", "decode_row"]),
    ("petastorm_tpu.converter", ["make_converter", "DatasetConverter"]),
    ("petastorm_tpu.etl.writer", ["write_dataset", "materialize_dataset",
                                  "stamp_dataset_metadata"]),
    ("petastorm_tpu.etl.metadata", ["open_dataset", "infer_or_load_schema",
                                    "DatasetInfo", "RowGroupRef"]),
    ("petastorm_tpu.etl.indexing", ["build_rowgroup_index", "get_row_group_indexes",
                                    "SingleFieldIndexer", "FieldNotNullIndexer"]),
    ("petastorm_tpu.cache", ["make_cache", "InMemoryCache", "LocalDiskCache",
                             "NullCache", "CacheBase"]),
    ("petastorm_tpu.cache_shared", ["SharedWarmCache"]),
    ("petastorm_tpu.fs", ["get_filesystem_and_path", "FilesystemFactory",
                          "normalize_dir_url"]),
    ("petastorm_tpu.retry", ["RetryPolicy", "retry_call", "resolve_retry_policy",
                             "CircuitBreaker", "make_circuit_breaker"]),
    ("petastorm_tpu.pool", ["make_executor", "WorkerError",
                            "PipelineStallError"]),
    ("petastorm_tpu.service.dispatcher", ["Dispatcher"]),
    ("petastorm_tpu.service.worker", ["ServiceWorker", "run_worker"]),
    ("petastorm_tpu.service.client", ["ServiceExecutor",
                                      "ServiceConnectionError"]),
    ("petastorm_tpu.service.autoscale", ["AutoscaleSupervisor",
                                         "AutoscalePolicy",
                                         "SubprocessSpawner",
                                         "InProcessSpawner",
                                         "ExecHookSpawner"]),
    ("petastorm_tpu.service.protocol", ["FrameSocket", "connect_frames",
                                        "parse_address", "encode_result",
                                        "PayloadDecoder", "WireItem"]),
    ("petastorm_tpu.service.wire", ["dumps", "loads", "encode_batch_parts",
                                    "decode_batch_body", "negotiate_codec",
                                    "WireFormatError"]),
    ("petastorm_tpu.errors", None),
    ("petastorm_tpu.ops.normalize", ["normalize_images"]),
    ("petastorm_tpu.ops.augment", ["random_crop", "random_flip",
                                   "random_crop_flip", "random_resized_crop",
                                   "crop_boxes", "resize_images", "mixup",
                                   "cutmix"]),
    ("petastorm_tpu.ops.jpeg", ["decode_coefficients", "finish_canvas",
                                "decode_from_layout", "decode_jpeg_column"]),
    ("petastorm_tpu.ops.ring_attention", ["ring_attention", "ring_attention_sharded"]),
    ("petastorm_tpu.ops.ulysses", ["ulysses_attention", "ulysses_attention_sharded"]),
    ("petastorm_tpu.parallel.mesh", ["local_data_slice", "shard_options_from_jax",
                                 "data_parallel_mesh", "sharding_for_batch"]),
    ("petastorm_tpu.parallel.selfcheck", ["run_selfcheck",
                                 "run_context_parallel_check",
                                 "run_distributed_write_check",
                                 "run_mesh2d_check"]),
    ("petastorm_tpu.parallel.write", ["distributed_write_dataset"]),
    ("petastorm_tpu.tools.copy_dataset", ["copy_dataset"]),
    ("petastorm_tpu.tools.show_metadata", ["describe"]),
    ("petastorm_tpu.telemetry", ["Telemetry", "NullTelemetry",
                                 "MetricsRegistry", "Counter", "Gauge",
                                 "Histogram", "TraceBuffer", "resolve",
                                 "enable", "enabled_from_env",
                                 "render_pipeline_report", "dominant_stage"]),
    ("petastorm_tpu.telemetry.sampler", ["MetricsSampler", "flight_record",
                                         "dump_flight_record",
                                         "load_flight_records"]),
    ("petastorm_tpu.telemetry.export", ["MetricsExportServer",
                                        "render_prometheus", "write_jsonl"]),
    ("petastorm_tpu.autotune", ["AutotunePolicy", "AutotuneController",
                                "resolve_autotune"]),
    ("petastorm_tpu.planner", ["plan_reader", "PlanVerdict", "PlannedKnob",
                               "ProfileStore", "footer_stats",
                               "dataset_fingerprint", "schema_hash",
                               "build_profile", "write_profile"]),
    ("petastorm_tpu.tools.diagnose", ["run_diagnosis",
                                      "render_autotune_verdict",
                                      "render_planner_verdict",
                                      "render_liveness_verdict",
                                      "render_stream_digest",
                                      "render_watch_frame"]),
    ("petastorm_tpu.test_util.chaos", ["ChaosSpec", "ChaosWorker",
                                       "SimulatedWorkerCrash"]),
    ("petastorm_tpu.test_util.matrix", ["MatrixCell", "CellResult",
                                        "run_cell", "cell_kwargs",
                                        "service_fleet"]),
]


def _symbols(mod, names):
    if names is not None:
        out = []
        for n in names:
            if not hasattr(mod, n):
                raise SystemExit(f"API list names {mod.__name__}.{n}, which does"
                                 " not exist - update docs/gen_api_reference.py")
            out.append((n, getattr(mod, n)))
        return out
    names = getattr(mod, "__all__", None) or [
        n for n, v in vars(mod).items()
        if not n.startswith("_") and getattr(v, "__module__", None) == mod.__name__]
    return [(n, getattr(mod, n)) for n in sorted(names)]


def _signature(obj) -> str:
    import re

    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return ""
    # default-value reprs can embed memory addresses - unstable across runs
    return re.sub(r" at 0x[0-9a-f]+", " at 0x...", sig)


def _doc(obj) -> str:
    return inspect.getdoc(obj) or "*(undocumented)*"


def _method_doc(cls, mname, m) -> str:
    """Docstring of an override, inheriting the base contract through the MRO
    (an undocumented override of a documented base method is documented)."""
    d = inspect.getdoc(m)
    if d:
        return d
    for base in cls.__mro__[1:]:
        bm = base.__dict__.get(mname)
        if bm is not None:
            d = inspect.getdoc(bm)
            if d:
                return f"{d}\n\n*(contract inherited from `{base.__name__}.{mname}`)*"
    return "*(undocumented)*"


def _render_symbol(name, obj, depth=3) -> str:
    head = "#" * depth
    lines = []
    if inspect.isclass(obj):
        lines.append(f"{head} class `{name}{_signature(obj)}`\n")
        lines.append(_doc(obj) + "\n")
        for mname, m in sorted(vars(obj).items()):
            if mname.startswith("_") or not (inspect.isfunction(m)
                                             or isinstance(m, property)):
                continue
            if isinstance(m, property):
                lines.append(f"{'#' * (depth + 1)} property `{name}.{mname}`\n")
            else:
                lines.append(f"{'#' * (depth + 1)} `{name}.{mname}{_signature(m)}`\n")
            lines.append(_method_doc(obj, mname, m) + "\n")
    else:
        lines.append(f"{head} `{name}{_signature(obj)}`\n")
        lines.append(_doc(obj) + "\n")
    return "\n".join(lines)


def generate(out_dir: str) -> list:
    os.makedirs(out_dir, exist_ok=True)
    index = ["# petastorm-tpu API reference",
             "",
             "Generated by `python docs/gen_api_reference.py` - regenerate"
             " after changing public signatures or docstrings.",
             ""]
    written = []
    for module_name, names in API:
        mod = importlib.import_module(module_name)
        slug = module_name.replace(".", "_") + ".md"
        parts = [f"# `{module_name}`\n"]
        mod_doc = inspect.getdoc(mod)
        if mod_doc:
            parts.append(mod_doc + "\n")
        syms = _symbols(mod, names)
        for name, obj in syms:
            parts.append(_render_symbol(name, obj))
        path = os.path.join(out_dir, slug)
        with open(path, "w") as f:
            f.write("\n".join(parts))
        written.append(path)
        first = (mod_doc or "").splitlines()[0] if mod_doc else ""
        index.append(f"- [`{module_name}`]({slug}) — {first}"
                     f" ({', '.join(n for n, _ in syms)})")
    index_path = os.path.join(out_dir, "README.md")
    with open(index_path, "w") as f:
        f.write("\n".join(index) + "\n")
    written.append(index_path)
    return written


if __name__ == "__main__":
    out = generate(os.path.join(os.path.dirname(os.path.abspath(__file__)), "api"))
    print(f"wrote {len(out)} files under docs/api/")
