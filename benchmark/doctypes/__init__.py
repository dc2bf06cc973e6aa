"""Document models: one module per doc type, found by the `doc_type` of
a configuration file."""
