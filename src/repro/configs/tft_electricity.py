"""Temporal Fusion Transformer at its published Electricity settings
(Lim, Arik, Loeff and Pfister, arXiv:1912.09363, IJF 2021; google-research
``tft/data_formatters/electricity.py``): hidden size 160, 4 attention heads,
one LSTM layer and one attention layer, dropout 0.1, look-back 168 and
horizon 24, quantiles 0.1 / 0.5 / 0.9, minibatch 64, learning rate 0.001
and a gradient clip at global norm 0.01.

As the fleet's forecaster the static input is the stream's id (Electricity
has one per customer; here one per stream of an 8-stream fleet), the
observed inputs are the 5 turbine channels, and the 3 known inputs stand
for Electricity's hour, day of week and hours from start
(``repro.core.windows.known_inputs``).
"""
from repro.configs.base import ModelConfig, TFTConfig

CONFIG = ModelConfig(
    name="tft-electricity",
    family="tft",
    n_layers=1,
    d_model=160,
    n_heads=4,
    n_kv_heads=4,
    d_ff=160,
    vocab_size=0,
    attention="full",
    dtype="float32",
    param_dtype="float32",
    tft=TFTConfig(hidden=160, n_heads=4, lookback=168, horizon=24,
                  n_observed=5, n_known=3, n_ids=8,
                  quantiles=(0.1, 0.5, 0.9), dropout=0.1),
    max_grad_norm=0.01,
    citation="Lim et al. 2021, arXiv:1912.09363, Electricity",
)
