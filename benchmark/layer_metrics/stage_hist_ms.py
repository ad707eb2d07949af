"""Device ms a round building histograms: the Pallas kernels
(``xtpu.kernel.*``), quantisation (``xtpu.quantise``), dequantisation and
folds (``xtpu.fold``) and what is left directly under ``xtpu.hist`` /
``xtpu.advance_hist``."""


def read(facts):
    from lib.program_trace import stage_group_ms
    return stage_group_ms(facts, "hist")
