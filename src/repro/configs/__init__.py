"""Model configurations: the paper's ConvCoTM geometries and the Table III
composite (``repro.configs.convcotm``), named by ``--arch``."""
