"""What the document models share."""

ROOT_ID = '00000000-0000-0000-0000-000000000000'
