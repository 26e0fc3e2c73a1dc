"""Benchmark driver (repo-root entry the round driver runs).

The implementation lives in bigdl_tpu.tools.bench_cli so installed copies
get the same driver via the `bigdl-tpu-bench` console script; see that
module's docstring for metric definitions.
"""

from bigdl_tpu.tools.bench_cli import bench_resnet50, main  # noqa: F401

if __name__ == "__main__":
    main()
