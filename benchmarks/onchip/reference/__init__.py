"""Plain references, one module per architecture family; a configuration
file names its module under the key "reference"."""
