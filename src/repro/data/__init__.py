"""Seeded data: the SSB generator and query registry, and synthetic star schemas."""
from .ssb import SSBData, generate as generate_ssb
from .ssb_queries import (PREDICTIVE_QUERIES, QUERIES, QUERY_IR,
                          compiled_plan, predictive_query_names,
                          query_groups, ssb_catalog, ssb_session)
from .synthetic import (SyntheticStar, cardinalities, feature_widths,
                        generate as generate_star, table4_catalog)

__all__ = ["SSBData", "generate_ssb", "QUERIES", "QUERY_IR",
           "PREDICTIVE_QUERIES", "compiled_plan", "predictive_query_names",
           "query_groups", "ssb_catalog", "ssb_session",
           "SyntheticStar", "cardinalities", "feature_widths",
           "generate_star", "table4_catalog"]
