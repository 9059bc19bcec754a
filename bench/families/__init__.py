"""One module per architecture family, found by the configuration's
`family`: the program's `ModelConfig` for a configuration file
(`program_config`), the benchmark's weight layout (`leaf_table`), and the
regrouping of those weights into the program's parameter tree
(`to_program_tree`)."""
