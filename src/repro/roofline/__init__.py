from repro.roofline.analysis import (HARDWARE, HW_V5E, CollectiveStats,
                                     collective_bytes_from_hlo,
                                     collective_stats_from_hlo,
                                     hardware_for, roofline_terms,
                                     model_flops)
