"""A model family that a test adds to a benchmark root as a file alone:
the paper CNN under another arch name, on images of a generator of its
own (three prototypes a class over a bank of two textures)."""
from chip_bench.families import paper_cnn

GENERATORS = {"checker_like": (dict(seed=4321, per_class=3, bank_size=2),
                               20_000,
                               dict(shift=2, noise=0.25,
                                    contrast_jitter=0.1))}


def render(data_spec, seed):
    return paper_cnn.render(data_spec, seed, GENERATORS)


forward_flops = paper_cnn.forward_flops
reference_model = paper_cnn.reference_model
program_kwargs = paper_cnn.program_kwargs
