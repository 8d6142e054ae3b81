"""The reference's own grouping rule gives the program's wavefront order
for the MLLM cell's configuration and mix."""
from benchmarks.chip import gen, harness
from benchmarks.chip.reference import mllm as ref


def test_reference_order_is_the_programs():
    from repro.core import cost_model
    from repro.core.executor import order_samples
    from repro.core.types import ParallelConfig
    from repro.mllm import workload as mllm
    cfg, tr = harness.config("pixtral-12b-mllm"), harness.traffic("mixed50")
    vit, lm = harness.path_module(tr["path"]).arch_configs(cfg)
    graph = mllm.mllm_spec(vit, lm, vit_parallel=ParallelConfig(),
                           lm_parallel=ParallelConfig(),
                           global_batch=tr["batch"], seq_len=tr["seq_len"],
                           mbs=tr["mbs"]).to_graph()
    rng = gen.rng_for(2**31 + 7)
    for _ in range(20):
        has = rng.permutation(tr["batch"]) < round(tr["batch"] *
                                                   tr["vision_ratio"])
        samples = cost_model.sample_tuples(graph, {"vit": has},
                                           tr["seq_len"], n=len(has))
        order, _ = order_samples(samples, reorder=True)
        assert list(order) == ref.wavefront_order(has), has
        assert sorted(ref.interleaved_order(has)) == list(range(len(has)))
        assert ref.interleaved_order(has) != list(order)
    assert ref.wavefront_order([1, 0, 1, 0]) == [2, 0, 3, 1]
