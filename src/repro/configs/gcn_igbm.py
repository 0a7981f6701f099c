"""Paper's own primary configuration: 3-/5-layer GCN, hidden 256, on
IGBM-scale graphs (10M nodes / 120M edges / 1024 features) — the GriNNder
evaluation setting (paper §8.1). Used by the SSO-engine launchers and
``chip_smoke.py`` at its published widths, not a dry-run cell."""
from repro.configs.builders import GNNArch, make_gnn_arch

CONFIG_3L = GNNArch(
    name="gcn-igbm-3l", model="gcn", n_layers=3, d_hidden=256,
    dataset="igbm", note="paper default (Table 1, L=3)",
)
CONFIG_5L = GNNArch(
    name="gcn-igbm-5l", model="gcn", n_layers=5, d_hidden=256,
    dataset="igbm", note="paper deep setting (Table 1, L=5)",
)

ARCH = make_gnn_arch(CONFIG_3L, __doc__.strip())
